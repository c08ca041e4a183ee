"""Probe: ``nn.layers.blocked_cross_entropy`` alone, forward + backward.

The head of ``lfm2-fit-s8192-b4`` by default (one pass bf16[4, 8192, 2048]
against a float32 table [8192, 2048]); ``--batch 1 --seq 4096 --vocab 49152
--passes 4 --head`` is ``ouro-fit-s4096-b1``'s. Each ``--tile ROWSxCOLUMNS``
runs the rule under that tile in place of the one it would choose (``rule``:
what the tree's own rule gives, the only form a tree before PR 38 takes);
the table beside ``HEAD_LOGIT_BYTES`` is read from this.

Without a chip (``JAX_PLATFORMS=cpu``) it compiles for a described v5e and
reads ``memory_analysis()`` and the float32 values of rows x nIn elements
in the compiled text. On the chip it also times the call. Runs the tree it
is pointed at (``--root .scratch_checkout/parent``), one tree a process; a
JSON line a tile, appended to ``chiprun_out/head_tiles.jsonl``.
"""

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--head", action="store_true",
                    help="a head [nIn, nOut], not an embedding's table")
    ap.add_argument("--tile", nargs="*", default=["rule"])
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn import layers as L

    N, T, D, V, P = args.batch, args.seq, args.width, args.vocab, args.passes
    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        place = {}
    else:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = {"sharding": SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])}
    shapes = ((jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16, **place),) * P,
              jax.ShapeDtypeStruct((D, V) if args.head else (V, D),
                                   jnp.float32, **place),
              jax.ShapeDtypeStruct((N, T), jnp.int32, **place))

    def loss(hs, w, y):
        return jnp.mean(L.blocked_cross_entropy(hs, w, y,
                                                table=not args.head))
    rule = getattr(L, "_head_tile", None)
    for tile in args.tile:
        if tile != "rule":
            forced = tuple(int(n) for n in tile.split("x"))
            L._head_tile = lambda rows, n_out, forced=forced: forced
        elif rule is not None:
            L._head_tile = rule
        out = {"tag": args.tag, "tile": tile, "rows": N * T, "vocab": V,
               "passes": P, "chosen": list(L._head_tile(N * T, V))
               if rule is not None else [N * T, L._head_block(N * T, V)]}
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        t0 = time.time()
        compiled = step.lower(*shapes).compile()
        out["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        out["temp_mib"] = round(ma.temp_size_in_bytes / 2 ** 20, 1)
        text = compiled.as_text()
        out["f32_rows_x_nin"] = text.count(f"f32[{N},{T},{D}]") \
            + text.count(f"f32[1,{N * T},{D}]")
        if on_chip:
            ks = jax.random.split(jax.random.PRNGKey(0), P + 2)
            hs = tuple(jax.random.normal(k, (N, T, D), jnp.bfloat16)
                       for k in ks[:P])
            w = jax.random.normal(ks[P], shapes[1].shape) * D ** -0.5
            y = jax.random.randint(ks[P + 1], (N, T), 0, V)
            jax.block_until_ready(compiled(hs, w, y))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                got = compiled(hs, w, y)
            jax.block_until_ready(got)
            out["ms_a_call"] = round(
                (time.perf_counter() - t0) / args.calls * 1e3, 3)
            out["loss"] = float(got[0])
            out["device"] = jax.devices()[0].device_kind
        print(json.dumps(out), flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/head_tiles.jsonl", "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
