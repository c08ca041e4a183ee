"""Probe: where a large weight's gradient meets Adam's update.

One projection x [rows, 2048] @ W [2048, n] (``--what proj``) or one
``GatedMLP`` of ``n`` inner units (``--what mlp``), forward + backward +
Adam's update of every leaf through the train step's own
``_process_and_apply_grads``, at each ``--rows`` and each ``--hidden`` n.
Each ``--form`` lowers the leaves' casts one way: ``rule`` is what the
tree's own ``policy_cast`` chooses (the only form a tree before the rule
takes: fused), ``fused`` and ``apart`` force it by setting
``UPDATE_APART_ROWS`` (as ``probe_head_tiles.py`` forces ``_head_tile``).

Without a chip (``JAX_PLATFORMS=cpu``) it compiles for a described v5e and
prints the compiler's plan: ``est_ms`` (the ``estimated_cycles`` of every
fusion, summed, over 1.5 GHz), and of each output fusion (the products)
its estimate and its output window. On the chip it also times the call.
Runs the tree it is pointed at (``--root``), one tree a process; a JSON
line a case, appended to ``chiprun_out/update_apart.jsonl``.
"""

import argparse
import itertools
import json
import os
import re
import sys
import time
import types

_WINDOW = re.compile(r'"output_window_bounds":\[([^\]]*)\]')
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_CLOCK_HZ = 1.5e9


def plan(text):
    """``(ms of every fusion's estimate, [(fusion, ms, output window)] of
    the output fusions: the products)`` from a compiled program's text."""
    total, products = 0, []
    for line in text.split("\n"):
        cycles = _CYCLES.search(line)
        if not cycles or " fusion(" not in line:
            continue
        total += int(cycles.group(1))
        window = _WINDOW.search(line)
        if "kind=kOutput" in line and window:
            products.append((
                _NAME.match(line).group(1),
                round(int(cycles.group(1)) / _CLOCK_HZ * 1e3, 3),
                "x".join(re.findall(r"\d+", window.group(1)))))
    return round(total / _CLOCK_HZ * 1e3, 3), products


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--what", nargs="*", default=["proj", "mlp"])
    ap.add_argument("--rows", nargs="*", type=int,
                    default=[4096, 8192, 16384, 32768])
    ap.add_argument("--hidden", nargs="*", type=int,
                    default=[512, 2048, 6144, 11776])
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--form", nargs="*", default=["fused", "apart"])
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.multilayer import _process_and_apply_grads
    from deeplearning4j_tpu.train import updaters

    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        place = {}
    else:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = {"sharding": SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])}
    adam = updaters.Adam(3e-4, beta1=0.9, beta2=0.95, epsilon=1e-8)
    settings = types.SimpleNamespace(grad_norm=None)
    rule = (getattr(L, "UPDATE_APART_ROWS", None),
            getattr(L, "UPDATE_APART_ELEMENTS", None))
    D = args.width
    for what, rows, n, form in itertools.product(
            args.what, args.rows, args.hidden, args.form):
        if form != "rule" and rule[0] is None:
            continue        # a tree without the rule lowers one form only
        if rule[0] is not None:
            L.UPDATE_APART_ROWS, L.UPDATE_APART_ELEMENTS = {
                "apart": (-1, 0), "fused": (1 << 62, 0)}.get(form, rule)
        T = min(rows, 8192)
        if what == "mlp":
            layer = L.GatedMLP(nHidden=n)
            layer.infer_nin(InputType.recurrent(D, T))
            shapes, n_out = layer.param_shapes(), D

            def apply(p, x, layer=layer):
                return layer.apply(p, {}, x, True, None)[0]
        else:
            layer = types.SimpleNamespace()
            shapes, n_out = {"W": (D, n)}, n

            def apply(p, x):
                return x @ p["W"]

        def step(p, opt, x, dy, t, layer=layer, apply=apply):
            def loss(p, x):
                cast, xb = L.policy_cast(layer, p, x, jnp.bfloat16)
                return jnp.sum((apply(cast, xb) * dy).astype(jnp.float32))
            g, dx = jax.grad(loss, (0, 1))(p, x)
            p, opt = _process_and_apply_grads(settings, adam, p, g, opt, t)
            return p, opt, dx

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, **place)
        p_s = {k: sds(s, jnp.float32) for k, s in shapes.items()}
        o_s = {k: {"m": v, "v": v} for k, v in p_s.items()}
        x_s = sds((rows // T, T, D), jnp.bfloat16)
        dy_s = sds((rows // T, T, n_out), jnp.bfloat16)
        fn = jax.jit(step, donate_argnums=(0, 1))
        t0 = time.time()
        compiled = fn.lower(p_s, o_s, x_s, dy_s,
                            sds((), jnp.float32)).compile()
        text = compiled.as_text()
        est_ms, products = plan(text)
        out = {"tag": args.tag, "what": what, "rows": rows, "hidden": n,
               "form": form, "compile_s": round(time.time() - t0, 1),
               "temp_mib": round(compiled.memory_analysis()
                                 .temp_size_in_bytes / 2 ** 20, 1),
               "est_ms": est_ms, "products": products}
        if on_chip:
            ks = jax.random.split(jax.random.PRNGKey(rows + n), 3 + len(p_s))
            p = {k: 0.02 * jax.random.normal(key, s.shape)
                 for (k, s), key in zip(p_s.items(), ks[3:])}
            opt = {k: adam.init_state(v) for k, v in p.items()}
            x = jax.random.normal(ks[0], x_s.shape, jnp.bfloat16)
            dy = jax.random.normal(ks[1], dy_s.shape, jnp.bfloat16)
            t = jnp.zeros((), jnp.float32)
            p, opt, dx = compiled(p, opt, x, dy, t)
            jax.block_until_ready(p)
            t0 = time.perf_counter()
            for _ in range(args.calls):
                p, opt, dx = compiled(p, opt, x, dy, t)
            jax.block_until_ready((p, dx))
            out["ms_a_call"] = round(
                (time.perf_counter() - t0) / args.calls * 1e3, 3)
            out["device"] = jax.devices()[0].device_kind
        print(json.dumps(out), flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/update_apart.jsonl", "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
