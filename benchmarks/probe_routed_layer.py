"""Probe: one ``SparseExpertsLayer`` forward + backward at a cell's size.

The routed path of ``lfm2-fit-s8192-b4`` (``--tokens 32768 --width 2048
--hidden 1536``) or ``xing4-fit-s4096-b1`` (``--tokens 4096 --width 3584
--hidden 1024``): 8 of 64 experts held, 4 a token, bf16, no shared expert,
under ``jax.checkpoint`` as a rematerialised stretch runs it (forward,
forward again, backward). ``--held-share`` plants a selection bias so
that about that share of the pairs meets a held expert (0.125 is uniform
routing; above ``ROUTED_ROWS_OVER_UNIFORM`` times that the layer takes a
second pass).

Without a chip (``JAX_PLATFORMS=cpu``) it compiles for a described v5e
and reads the compiled text: tensors of ``tokens x 4`` rows and width C
or F, the gathers' bytes, the grouped-product kernels, conditionals,
``memory_analysis()``. On the chip it also times the step. Runs the tree
it is pointed at (``--root .scratch_checkout/parent``), one tree a
process; a JSON line a run, appended to ``chiprun_out/routed_layer.jsonl``.
"""

import argparse
import json
import os
import re
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=1536)
    ap.add_argument("--held-share", type=float, nargs="*", default=[0.125])
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn.config import InputType

    M, C, F, k, E, n = args.tokens, args.width, args.hidden, 4, 8, 64
    layer = L.SparseExpertsLayer(nExperts=n, nExpertsPerTok=k, nHidden=F,
                                 heldExperts=list(range(E)),
                                 nSharedExperts=0, weightInit="xavier")
    layer.infer_nin(InputType.recurrent(C, M))
    shapes, state = jax.eval_shape(layer.initialize, jax.random.PRNGKey(0))

    def loss(params, state, x, cot):
        run = jax.checkpoint(lambda p, x: layer.apply(
            p, state, x, True, jax.random.PRNGKey(0)))
        out, new = run(params, x)
        return jnp.sum(out.astype(jnp.float32) * cot), new

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 2), has_aux=True))
    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        place = lambda s: s                                    # noqa: E731
    else:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        place = lambda s: jax.ShapeDtypeStruct(               # noqa: E731
            s.shape, s.dtype, sharding=chip)
    low = lambda s: jax.ShapeDtypeStruct(                     # noqa: E731
        s.shape, jnp.float32 if s.shape[-1] == n and len(s.shape) == 2
        else jnp.bfloat16)
    p_abs = {name: place(low(s)) for name, s in shapes.items()}
    s_abs = jax.tree_util.tree_map(place, state)
    x_abs = place(jax.ShapeDtypeStruct((1, M, C), jnp.bfloat16))
    t0 = time.time()
    compiled = step.lower(p_abs, s_abs, x_abs, x_abs).compile()
    out = {"tag": args.tag, "tokens": M, "width": C, "hidden": F,
           "compile_s": round(time.time() - t0, 1)}
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    out["temp_gib"] = round(mem.temp_size_in_bytes / 2 ** 30, 4)
    wide = re.findall(r"(?:bf16|f32)\[%d,(?:%d|%d)\]" % (M * k, C, F), text)
    view = re.findall(r"(?:bf16|f32)\[%d,%d,(?:%d|%d)\]" % (M, k, C, F), text)
    out["pair_row_tensors"] = len(wide) + len(view)
    out["kernels"] = len(re.findall(
        r"= [^=]*custom-call\([^\n]*ragged-dot(?!-metadata)", text))
    out["conditionals"] = len(re.findall(r" conditional\(", text))
    gathers = 0
    for line in text.split("\n"):
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (bf16|f32)\[(\d+),(\d+)\]"
                     r"[^ ]* (?:gather|fusion)\(", line)
        if m and ("gather" in line.split("metadata")[0]
                  or "kind=kCustom" in line) and int(m.group(3)) in (C, F):
            gathers += int(m.group(2)) * int(m.group(3)) * (
                2 if m.group(1) == "bf16" else 4)
    out["gathered_mib"] = round(gathers / 2 ** 20, 1)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/routed_layer_{args.tag}_{M}.hlo.txt", "w") as fh:
        fh.write(text)
    if on_chip:
        key = jax.random.PRNGKey(1)
        params = {name: (jax.random.normal(jax.random.fold_in(key, i),
                                           s.shape, jnp.float32)
                         * (s.shape[-2] ** -0.5)).astype(s.dtype)
                  for i, (name, s) in enumerate(sorted(p_abs.items()))}
        x = jax.random.normal(key, (1, M, C), jnp.float32).astype(jnp.bfloat16)
        for share in args.held_share:
            # a bias on the held experts that lifts their share of the
            # selected pairs to about `share` (found by bisection on the
            # host: the scores are sigmoids of N(0, 1) products)
            s = 1 / (1 + np.exp(-np.asarray(
                x[0, :2048].astype(jnp.float32)
                @ params["Wr"].astype(jnp.float32))))
            lo_b, hi_b = -2.0, 2.0
            for _ in range(30):
                mid = (lo_b + hi_b) / 2
                top = np.argsort(-(s + np.where(np.arange(n) < E, mid, 0)),
                                 axis=-1)[:, :k]
                lo_b, hi_b = (mid, hi_b) if (top < E).mean() < share \
                    else (lo_b, mid)
            st = dict(jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), state))
            st["select_bias"] = jnp.where(jnp.arange(n) < E, mid, 0.0)
            (val, new), grads = compiled(params, st, x, x)
            jax.block_until_ready(grads)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                (val, new), grads = compiled(params, st, x, x)
            jax.block_until_ready(grads)
            ms = (time.perf_counter() - t0) / args.steps * 1e3
            run = dict(out, held_share_asked=share, step_ms=round(ms, 3),
                       held_pairs=float(new["expert_load"].sum()),
                       loss=float(val),
                       grad_norm=float(jnp.sqrt(sum(
                           jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in jax.tree_util.tree_leaves(grads)))))
            if "pass_steps" in new:
                run["pass_steps"] = [float(v) for v in new["pass_steps"]]
            print(json.dumps(run), flush=True)
            with open("chiprun_out/routed_layer.jsonl", "a") as fh:
                fh.write(json.dumps(run) + "\n")
    else:
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
