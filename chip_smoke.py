"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process, one TPU chip: ResNet-50 ``fit()`` on the default path and on
the configuration ``bench.py`` measures (bf16 policy, NHWC, fused epilogues,
the Pallas overrides installed), the same net behind ``ModelServer`` and
``HttpIngress``, each Pallas override against its generic op, and two more
model paths (TinyYOLO bf16+fused, a LayerNorm net under the overrides).
``--chips 4`` runs only the path that
exists across chips — ``GSPMDTrainer`` with ZeRO over a ``data=4`` mesh —
beside the same steps on device 0.

Standard output carries one JSON line per phase and, last, the contract's
line: ``{"ok": true, "device": {"platform", "kind", "count"}}``. Any phase
that raises ends the run non-zero with no such line. Without a TPU the
script fails before doing any work; ``--rehearse`` (sizes shrunk from the
command line, kernels in the Pallas interpreter) walks the same control
flow off the chip and still exits non-zero, with no last line. Nothing here
is a benchmark: img/s is a smoke number.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # four chips, the sharded path only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --batch 4 --image 32 \\
        --yolo-batch 2 --yolo-image 64  # CPU rehearsal, exits 3
"""

import argparse
import collections
import gc
import json
import logging
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

SEED = 22
N_CLASSES = 1000
#: same-seed fp32-vs-bf16 loss curves: every step within this share of the
#: curve's scale, its first loss — the bound of ``bench.py:_loss_parity``
LOSS_PARITY_BOUND = 0.10
#: four chips against one on the same bf16 program:
#: ``tests/test_distributed.py`` holds fp32 parameters of an MLP to 2e-6;
#: here the activations are bf16 (eps 2**-8) and the batch statistics and
#: gradients are reduced across chips in another order, so per-step losses
#: are held to this share of the first loss
GSPMD_LOSS_BOUND = 0.02
BF16_EPS = 2.0 ** -8


def last_line(device, n_devices: int) -> dict:
    """The contract's last line, as an object: these keys and no others."""
    return {"ok": True,
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": n_devices}}


class Phases:
    """Prints one JSON line per phase, with what every line carries: peak
    device memory (process-wide high-water marks) and the compile cache's
    place and traffic."""

    def __init__(self, devices, cache_dir: str):
        import jax
        self.devices = devices
        self.cache_dir = cache_dir
        self.cache_warm = os.path.isdir(cache_dir) and bool(
            os.listdir(cache_dir))
        self._cache_events = collections.Counter()
        jax.monitoring.register_event_listener(self._on_jax_event)

    def _on_jax_event(self, event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            self._cache_events[event.rsplit("/", 1)[1]] += 1

    def emit(self, name: str, **fields):
        # JAX counts a cache write as a "miss"; programs that compile in
        # under a second are neither written nor counted
        events, self._cache_events = self._cache_events, collections.Counter()
        stats = [d.memory_stats() or {} for d in self.devices]
        print(json.dumps({
            "phase": name, **fields,
            # live buffers, and what compiled programs reserve for their
            # temporaries: the device's high-water mark is about their sum
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "peak_bytes_reserved": [s.get("peak_bytes_reserved")
                                    for s in stats],
            "compile_cache": {"dir": self.cache_dir,
                              "warm_at_start": self.cache_warm,
                              "hits": events["cache_hits"],
                              "writes": events["cache_misses"]},
        }), flush=True)


def _timed_fits(net, data, n: int, **fit_kw):
    """``n`` calls of ``net.fit``; each time ends after the score listener
    pulled the step's loss to the host (a block_until_ready on it)."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        net.fit(data, **fit_kw)
        out.append(time.perf_counter() - t0)
    return out


def _split(first: float, steady) -> dict:
    """Seconds of a phase split into compilation (the first call less one
    steady call) and steady state."""
    med = float(np.median(steady))
    return {"compile": round(max(first - med, 0.0), 3),
            "steady": round(float(np.sum(steady)), 4),
            "first_call": round(first, 3)}


def _finite(losses, what: str):
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss in {losses}")


def _close(got, want, rtol: float, atol: float, what: str) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: non-finite values")
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    if bad.any():
        raise AssertionError(
            f"{what}: {int(bad.sum())} of {bad.size} values differ beyond "
            f"rtol={rtol} atol={atol}; max abs err {float(err.max()):.4g}")
    return float(err.max())


def _resnet(args):
    from deeplearning4j_tpu.models import zoo
    return zoo.ResNet50(num_classes=N_CLASSES,
                        input_shape=(3, args.image, args.image)).init()


def _optimize(net):
    """The configuration bench.py measures (``_CnnBench._optimize``)."""
    net.setPrecisionPolicy("bf16")
    net.setComputeLayout("NHWC")
    net.setEpilogueFusion(True)
    return net


def _score_listener(net):
    from deeplearning4j_tpu.train.listeners import ScoreIterationListener
    lst = ScoreIterationListener(print_iterations=10 ** 9)
    net.setListeners(lst)
    return lst


def _image_batch(rng, batch: int, image: int):
    x = rng.standard_normal((batch, 3, image, image), dtype=np.float32)
    y = np.eye(N_CLASSES, dtype=np.float32)[
        rng.integers(0, N_CLASSES, batch)]
    return x, y


# ------------------------------------------------------------------- train
def phase_train(args, phases):
    """ResNet-50 through ``net.fit``: default path, then optimized."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet

    x, y = _image_batch(np.random.default_rng(SEED), args.batch, args.image)
    on_device = DataSet(jnp.asarray(x), jnp.asarray(y))

    # default path: fp32, NCHW, unfused, no override installed
    net = _resnet(args)
    scores = _score_listener(net)
    first = _timed_fits(net, on_device, 1)[0]
    steady = _timed_fits(net, on_device, 2)
    default_losses = list(scores.history)
    _finite(default_losses, "train_default")
    phases.emit("train_default", seconds=_split(first, steady),
                step_seconds=steady, losses=default_losses,
                img_per_sec=round(args.batch / float(np.median(steady)), 1),
                batch=args.batch, image=args.image)
    del net, scores
    gc.collect()    # a full-size fp32 net and the next one do not both fit

    # from here to the end of the run the overrides are installed
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    pk.install_platform_overrides(interpret=args.rehearse)
    net = _optimize(_resnet(args))
    scores = _score_listener(net)
    first = _timed_fits(net, on_device, 1)[0]
    steady = _timed_fits(net, on_device, 5)
    scale = max(abs(default_losses[0]), 1e-6)
    parity = max(abs(a - b) / scale
                 for a, b in zip(default_losses, scores.history))
    if parity >= LOSS_PARITY_BOUND:
        raise AssertionError(
            f"loss parity {parity:.4f} >= {LOSS_PARITY_BOUND}: "
            f"fp32 {default_losses} vs bf16 {scores.history[:3]}")
    # one megastep dispatch from host batches, through the prefetcher
    t0 = time.perf_counter()
    net.fit([DataSet(x, y) for _ in range(4)], steps_per_dispatch=4)
    mega = time.perf_counter() - t0
    losses = list(scores.history)
    _finite(losses, "train_optimized")
    if len(losses) != 1 + 5 + 4:
        raise AssertionError(f"expected 10 steps, saw {len(losses)}")
    phases.emit("train_optimized", seconds=_split(first, steady),
                step_seconds=steady, megastep_k4_first_call_seconds=mega,
                losses=losses, loss_parity_max_rel=parity,
                img_per_sec=round(args.batch / float(np.median(steady)), 1),
                batch=args.batch, image=args.image)
    return net


# ------------------------------------------------------------------- serve
def phase_serve(args, phases, net):
    """The trained net behind ModelServer and HttpIngress; every answer
    against ``net.output`` called directly."""
    from deeplearning4j_tpu.serving.ingress import HttpIngress
    from deeplearning4j_tpu.serving.server import ModelServer

    rng = np.random.default_rng(SEED + 1)
    shape = (3, args.image, args.image)
    # bf16 compute on both sides, at different batch sizes: softmax
    # outputs within a few bf16 steps of each other
    rtol, atol = 16 * BF16_EPS, 1e-5
    sv = ModelServer(net, batch_limit=32)
    try:
        t0 = time.perf_counter()
        sv.warmup([shape])
        warm = time.perf_counter() - t0
        request_seconds, errs = {}, {}
        for rows in (1, 5, 32):
            x = rng.standard_normal((rows,) + shape, dtype=np.float32)
            t0 = time.perf_counter()
            got = sv.submit(x).get(120.0)
            request_seconds[rows] = time.perf_counter() - t0
            errs[rows] = _close(got, net.output(x), rtol, atol,
                                f"serve submit({rows})")
        x = rng.standard_normal((2,) + shape, dtype=np.float32)
        with HttpIngress(sv, port=0) as ing:
            req = urllib.request.Request(
                ing.url + "/v1/models/default:predict", data=x.tobytes(),
                headers={"Content-Type": "application/octet-stream",
                         "X-Tensor-Shape": ",".join(map(str, x.shape)),
                         "X-Tensor-Dtype": "float32"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120.0) as resp:
                status = resp.status
                body = json.load(resp)
            request_seconds["http_2"] = time.perf_counter() - t0
        if status != 200:
            raise AssertionError(f"predict answered {status}: {body}")
        errs["http_2"] = _close(body["predictions"], net.output(x), rtol,
                                atol, "serve POST predict")
        recompiles = sv.recompiles_after_warmup()
        if recompiles != 0:
            raise AssertionError(f"{recompiles} recompiles after warmup")
    finally:
        sv.close()
    stats = sv.stats()
    if (stats["state"], stats["queue_depth"], stats["counts"]) != (
            "closed", 0, {"completed": 4}):
        raise AssertionError(f"close() did not drain cleanly: {stats}")
    phases.emit("serve", seconds={"compile": round(warm, 3),
                                  "steady": round(sum(
                                      request_seconds.values()), 4)},
                request_seconds=request_seconds, max_abs_err=errs,
                buckets=sv.buckets(), recompiles_after_warmup=recompiles)


# ----------------------------------------------------------------- kernels
def _kernel_cases(interpret: bool):
    """(name, override fn, generic fn, arguments, rtol, atol): each
    override at one real shape of the models this repo trains."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import attention as attn_ops
    from deeplearning4j_tpu.ops import normalization as norm_ops
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    bf16, f32 = jnp.bfloat16, jnp.float32
    ln = pk.make_layer_norm_override(interpret)
    sm = pk.make_softmax_override(interpret)
    fa = pk.make_flash_attention_override(interpret)

    def attn_grad(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            fn(q, k, v).astype(f32))), argnums=(0, 1, 2))

    # arguments: (shape, dtype, mean, std) of the normal samples — gains
    # and shifts sized like a trained BatchNorm/LayerNorm's, not like noise
    def act(shape, dtype=bf16):
        return (shape, dtype, 0.0, 1.0)

    def gain(d, dtype):
        return ((d,), dtype, 1.0, 0.1)

    def shift(d, dtype):
        return ((d,), dtype, 0.0, 0.5)

    qkv = [act((32, 128, 12, 64))] * 3
    few = 8 * BF16_EPS      # a few bf16 steps on values of order one
    return [
        ("layer_norm_4096x768_bf16", ln, norm_ops.layer_norm,
         [act((4096, 768)), gain(768, f32), shift(768, f32)], few, few),
        # f32, but the chip's exp and divide are not XLA's bit for bit
        ("softmax_4096x1024_f32", sm, jax.nn.softmax,
         [act((4096, 1024), f32)], 1e-3, 1e-7),
        ("flash_attention_fwd_32x128x12x64_bf16", fa,
         attn_ops._flash_attention_scan, qkv, few, few),
        ("flash_attention_grad_32x128x12x64_bf16", attn_grad(fa),
         attn_grad(attn_ops._flash_attention_scan), qkv, few, few),
    ]


def phase_kernels(args, phases):
    """Each Pallas override, compiled, against its generic op."""
    import jax
    key = jax.random.PRNGKey(SEED)
    results = {}
    t_compile = t_steady = 0.0
    for name, kernel, generic, specs, rtol, atol in _kernel_cases(
            args.rehearse):
        keys = jax.random.split(key, len(specs))
        ins = [(mean + std * jax.random.normal(k, shape)).astype(dtype)
               for k, (shape, dtype, mean, std) in zip(keys, specs)]
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*ins).compile()
        t_compile += time.perf_counter() - t0
        if not args.rehearse and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in its HLO")
        jax.block_until_ready(compiled(*ins))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*ins))
        second = time.perf_counter() - t0
        want = jax.jit(generic)(*ins)
        leaves = zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want))
        results[name] = {
            "seconds": round(second, 6),
            "max_abs_err": max(_close(g, w, rtol, atol, name)
                               for g, w in leaves)}
        t_steady += second
    phases.emit("kernels", seconds={"compile": round(t_compile, 3),
                                    "steady": round(t_steady, 4)},
                kernels=results)


def phase_model_paths(args, phases):
    """One ``fit()`` step each of two more model paths: TinyYOLO's fused
    leaky epilogues (the compiler's own code, no kernel), and a LayerNorm
    net with the kernel shown in the compiled step; then a few steps of a
    tiny looped language model (``_looped_lm``)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.distributed.gspmd import compiled_train_step_hlo
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import (DenseLayer, LayerNorm,
                                              OutputLayer)
    from deeplearning4j_tpu.train import updaters

    rng = np.random.default_rng(SEED + 2)
    b, hw = args.yolo_batch, args.yolo_image
    yolo = _optimize(zoo.TinyYOLO(num_classes=20,
                                  input_shape=(3, hw, hw)).init())
    # bench.py's TinyYOLO batch: an empty-object label grid
    yolo_ds = DataSet(
        jnp.asarray(rng.standard_normal((b, 3, hw, hw), dtype=np.float32)),
        jnp.zeros((b, 24, hw // 32, hw // 32), jnp.float32))

    conf = (NeuralNetConfiguration.Builder().seed(SEED)
            .updater(updaters.Adam(1e-3)).list()
            .layer(DenseLayer(nOut=768, activation="relu"))
            .layer(LayerNorm())
            .layer(OutputLayer(nOut=16, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(256)).build())
    ln_net = MultiLayerNetwork(conf).init()
    ln_ds = DataSet(
        jnp.asarray(rng.standard_normal((512, 256), dtype=np.float32)),
        jnp.asarray(np.eye(16, dtype=np.float32)[rng.integers(0, 16, 512)]))

    out = {}
    for name, net, ds, has_kernel in (
            ("tiny_yolo_bf16_fused", yolo, yolo_ds, False),
            ("layer_norm_net", ln_net, ln_ds, True)):
        scores = _score_listener(net)
        first = _timed_fits(net, ds, 1)[0]
        second = _timed_fits(net, ds, 1)[0]
        _finite(scores.history, name)
        n_kernels = compiled_train_step_hlo(
            net, ds.features, ds.labels).count("tpu_custom_call")
        if not args.rehearse and bool(n_kernels) != has_kernel:
            raise AssertionError(
                f"{name}: {n_kernels} tpu_custom_call in the step")
        out[name] = {"seconds": _split(first, [second]),
                     "losses": list(scores.history),
                     "pallas_calls_in_step_hlo": n_kernels}
    out["looped_lm_bf16"] = _looped_lm(rng)
    phases.emit("model_paths", seconds={
        "compile": round(sum(v["seconds"]["compile"]
                             for v in out.values()), 3),
        "steady": round(sum(v["seconds"]["steady"]
                            for v in out.values()), 4)},
        yolo_batch=b, yolo_image=hw, **out)


def _looped_lm(rng, steps: int = 6):
    """A tiny looped language model (``zoo.Ouro``: a LoopVertex over two
    decoder layers run four times, the exit-weighted head) fitted on one
    token batch under bf16: finite, falling losses."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models import zoo
    net = zoo.Ouro(num_layers=2, hidden_size=64, num_heads=4, head_dim=16,
                   intermediate_size=176, vocab_size=512, total_ut_steps=4,
                   seq_len=32).init()
    net.setPrecisionPolicy("bf16")
    rows = rng.integers(0, 512, (4, 33)).astype(np.int32)
    ds = DataSet(rows[:, :-1], rows[:, 1:])
    scores = _score_listener(net)
    times = _timed_fits(net, ds, steps)
    losses = [float(v) for v in scores.history]
    _finite(losses, "looped_lm_bf16")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        raise AssertionError(f"looped_lm_bf16: the loss of one repeated "
                             f"batch does not fall: {losses}")
    return {"seconds": _split(times[0], times[1:]), "losses": losses}


def phase_pipeline_workers(phases):
    """Decode workers beside a parent that holds the chip: they are spawned
    pinned to the CPU, fill batches, and are joined."""
    from PIL import Image
    from deeplearning4j_tpu.data.pipeline import MultiWorkerImageIterator
    rng = np.random.default_rng(SEED + 3)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_jpegs_") as root:
        for c in range(2):
            os.makedirs(os.path.join(root, f"class{c}"))
            for i in range(8):
                Image.fromarray(rng.integers(0, 255, (48, 48, 3),
                                             dtype=np.uint8)).save(
                    os.path.join(root, f"class{c}", f"{i}.jpg"))
        t0 = time.perf_counter()
        it = MultiWorkerImageIterator(root, 32, 32, batch_size=8, workers=2)
        try:
            shapes = []
            while it.hasNext():
                shapes.append(np.asarray(it.next().features).shape)
        finally:
            it.close()
        if shapes != [(8, 3, 32, 32)] * 2:
            raise AssertionError(f"expected 2 batches of 8, got {shapes}")
        phases.emit("pipeline_workers",
                    seconds={"compile": 0.0,
                             "steady": round(time.perf_counter() - t0, 3)},
                    batches=len(shapes), workers=2)


# -------------------------------------------------------------- four chips
def phase_gspmd(args, phases):
    """``GSPMDTrainer`` with ZeRO over ``data=4`` beside the same steps on
    device 0, and proof that the work is spread over all four."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.distributed import (GSPMDTrainer,
                                                ShardedTrainingPlan,
                                                ZeroPlan)
    from deeplearning4j_tpu.distributed.gspmd import compiled_train_step_hlo
    from deeplearning4j_tpu.parallel.mesh import DeviceMesh

    devices = jax.devices()
    x, y = _image_batch(np.random.default_rng(SEED), args.batch, args.image)
    steps = 5

    def run(fit, net, ds):
        scores = _score_listener(net)
        first = _timed_fits(fit, ds, 1)[0]
        steady = _timed_fits(fit, ds, steps - 1)
        _finite(scores.history, "gspmd")
        return list(scores.history), _split(first, steady), steady

    # on both sides the batch is placed once, so a step holds no transfer
    # from the host and the two differ by the chips alone
    net = _resnet(args).setPrecisionPolicy("bf16")
    plan = ShardedTrainingPlan(DeviceMesh.create(data=4), zero=ZeroPlan())
    trainer = GSPMDTrainer(net, plan)
    placed = DataSet(plan.place(x), plan.place(y))
    losses4, seconds4, steady4 = run(trainer, net, placed)

    def device_set(tree):
        out = set()
        for leaf in jax.tree_util.tree_leaves(tree):
            out |= set(leaf.sharding.device_set)
        return out

    # the ZeRO-sharded leaves of the updater state, and a placed batch
    sharded_opt = [leaf for leaf in jax.tree_util.tree_leaves(net._opt_state)
                   if not leaf.sharding.is_fully_replicated]
    if not sharded_opt:
        raise AssertionError("ZeRO sharded no updater-state tensor")
    for what, held in (("updater state", device_set(sharded_opt)),
                       ("batch", device_set(placed.features))):
        if held != set(devices):
            raise AssertionError(
                f"{what} is on {sorted(d.id for d in held)}, "
                f"not on all of {[d.id for d in devices]}")
    shard_share = max(leaf.addressable_shards[0].data.nbytes / leaf.nbytes
                      for leaf in sharded_opt)
    if shard_share > 0.25 + 1e-9:
        raise AssertionError(
            f"a ZeRO leaf keeps {shard_share:.2f} of itself on one device")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if not args.rehearse and min(in_use) < 2 ** 20:
        raise AssertionError(f"a device holds almost nothing: {in_use}")
    hlo = compiled_train_step_hlo(net, placed.features, placed.labels)
    collectives = {k: hlo.count(k + "(") + hlo.count(k + "-start(")
                   for k in ("all-reduce", "reduce-scatter", "all-gather")}
    if collectives["all-reduce"] + collectives["reduce-scatter"] == 0:
        raise AssertionError("no all-reduce or reduce-scatter in the step")
    n_sharded = len(sharded_opt)
    del trainer, net, plan, sharded_opt, placed
    gc.collect()

    one = _resnet(args).setPrecisionPolicy("bf16")
    losses1, seconds1, steady1 = run(
        one, one, DataSet(jnp.asarray(x), jnp.asarray(y)))
    for leaf in jax.tree_util.tree_leaves(one._params):
        if leaf.sharding.device_set != {devices[0]}:
            raise AssertionError("the one-chip run left device 0")
    scale = max(abs(losses1[0]), 1e-6)
    worst = max(abs(a - b) / scale for a, b in zip(losses1, losses4))
    if worst >= GSPMD_LOSS_BOUND:
        raise AssertionError(
            f"GSPMD losses {losses4} leave one-chip losses {losses1}: "
            f"{worst:.4f} >= {GSPMD_LOSS_BOUND}")
    phases.emit("gspmd_4chip", seconds=seconds4, step_seconds=steady4,
                losses=losses4, one_chip_seconds=seconds1,
                one_chip_step_seconds=steady1, one_chip_losses=losses1,
                loss_max_rel_delta=worst, bytes_in_use=in_use,
                zero_sharded_tensors=n_sharded,
                zero_max_shard_share=shard_share,
                collectives_in_step_hlo=collectives,
                img_per_sec=round(args.batch / float(np.median(steady4)), 1),
                one_chip_img_per_sec=round(
                    args.batch / float(np.median(steady1)), 1),
                batch=args.batch, image=args.image)


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the GSPMD path and its one-chip twin")
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: walk the phases with interpreted "
                         "kernels, then exit non-zero with no last line")
    ap.add_argument("--batch", type=int, default=256,
                    help="ResNet-50 training batch (bench.py's 256)")
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--yolo-batch", type=int, default=32)
    ap.add_argument("--yolo-image", type=int, default=416)
    args = ap.parse_args(argv)

    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)
    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if on_tpu and args.rehearse:
        print("chip_smoke.py: --rehearse is for a machine without a TPU; on "
              "the chip the kernels run compiled", file=sys.stderr)
        return 2
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke.py: no TPU: jax found {devices[0].platform} "
              f"{devices[0].device_kind!r}", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but jax found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from deeplearning4j_tpu.utils.environment import place_jax_compile_cache
    phases = Phases(devices, place_jax_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_gspmd(args, phases)
    else:
        # the cheapest phase first: a kernel the chip refuses then costs
        # seconds of chip time, not the minutes of the ResNet-50 compiles
        phase_kernels(args, phases)
        net = phase_train(args, phases)     # installs the overrides
        phase_serve(args, phases, net)
        del net
        gc.collect()
        phase_model_paths(args, phases)
        phase_pipeline_workers(phases)
    print(f"chip_smoke.py: every phase passed in "
          f"{time.perf_counter() - t0:.1f}s; device 0 memory_stats: "
          f"{devices[0].memory_stats()}", file=sys.stderr)
    if args.rehearse:
        print("chip_smoke.py: rehearsal only — no chip, no result",
              file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(last_line(devices[0], len(devices))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
