"""Probe: the ISSUE-14 device-timing bridge + fused-epilogue contracts.

Three asserted checks, printed as ONE JSON line (wired as
``bench.py --device-timing``):

1. **Non-empty attribution** — ``profiler.devicetime.measure`` over a
   conv fixture produces a per-layer table whose rows cover every layer,
   whose time shares sum to ~1, and whose per-layer FLOPs equal the
   analyzer's declared-shape model (the same numbers W105 reasons with).
2. **Fused epilogue, fp32** — the bias+BN+relu / BN+leaky epilogue
   path (NHWC + ``setEpilogueFusion``) is BIT-CLOSE to the reference
   path: forward max|Δ| and one-fit-step loss delta both under 1e-4.
3. **Fused epilogue, bf16** — under ``PrecisionPolicy("bf16")`` the
   fused+NHWC loss curve tracks the unfused bf16 curve within 10% of
   the curve scale (loss parity, the same guard the bench rows carry).

Run: python benchmarks/probe_device_timing.py [--quick]
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"   # a probe of contracts, not of speed
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_fixture(hw: int = 16, bn: bool = True, leaky: bool = False):
    from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (ActivationLayer,
                                              BatchNormalization,
                                              ConvolutionLayer, DenseLayer,
                                              OutputLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    b = (NeuralNetConfiguration.Builder().seed(7).weightInit("relu").list()
         .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1), nOut=16,
                                 activation="identity")))
    if bn:
        b = (b.layer(BatchNormalization())
             .layer(ActivationLayer("leakyrelu" if leaky else "relu")))
    b = (b.layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                  stride=(2, 2)))
         .layer(DenseLayer(nOut=32, activation="relu"))
         .layer(OutputLayer(nOut=5, lossFunction="mcxent",
                            activation="softmax"))
         .setInputType(InputType.convolutional(hw, hw, 3)))
    return MultiLayerNetwork(b.build()).init()


def check_attribution(out: dict, reps: int):
    from deeplearning4j_tpu.profiler import devicetime as dt
    net = build_fixture()
    rng = np.random.RandomState(0)
    x = rng.randn(8, 3, 16, 16).astype(np.float32)
    table = dt.measure(net, x, reps=reps, mode="sync")
    assert len(table.rows) == len(net.layers), \
        f"attribution covered {len(table.rows)}/{len(net.layers)} layers"
    share = sum(r.share for r in table.rows)
    assert abs(share - 1.0) < 1e-6, f"time shares sum to {share}"
    flops = dict((name, f) for name, _op, f
                 in dt.layer_flop_model(net.conf))
    for r in table.rows:
        expect = flops[r.layer] * 8 * 3.0     # batch x train factor
        assert r.flops == expect, \
            f"{r.layer}: table {r.flops} != FLOP model {expect}"
    assert table.top_offenders(1), "no offenders ranked"
    out["table_rows"] = len(table.rows)
    out["top_offender"] = table.top_offenders(1)[0]["layer"]
    out["flop_model_match"] = True


def _optimized(net):
    net.setComputeLayout("NHWC")
    net.setEpilogueFusion(True)
    return net


def check_fused_fp32(out: dict, leaky: bool):
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.RandomState(1)
    x = rng.randn(8, 3, 16, 16).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.randint(0, 5, 8)]
    a = build_fixture(leaky=leaky)
    b = _optimized(build_fixture(leaky=leaky))
    oa = np.asarray(a.output(x))
    ob = np.asarray(b.output(x))
    fwd = float(np.abs(oa - ob).max())
    a.fit(DataSet(x, y))
    b.fit(DataSet(x, y))
    loss = abs(a.score() - b.score())
    assert fwd < 1e-4, f"fused fp32 forward diverged: {fwd}"
    assert loss < 1e-4, f"fused fp32 fit loss diverged: {loss}"
    key = "fused_fp32_leaky" if leaky else "fused_fp32"
    out[key] = {"fwd_max_abs": fwd, "fit_loss_delta": loss}


def check_fused_bf16(out: dict, steps: int):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.RandomState(2)
    x = rng.randn(8, 3, 16, 16).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.randint(0, 5, 8)]
    ds = DataSet(x, y)
    a = build_fixture().setPrecisionPolicy("bf16")
    b = _optimized(build_fixture()).setPrecisionPolicy("bf16")
    la, lb = [], []
    for _ in range(steps):
        a.fit(ds)
        la.append(float(a.score()))
        b.fit(ds)
        lb.append(float(b.score()))
    scale = max(abs(la[0]), 1e-6)
    rel = max(abs(p - q) / scale for p, q in zip(la, lb))
    assert rel < 0.10, f"bf16 fused loss parity broke: {rel}"
    out["bf16_parity_max_rel"] = round(rel, 6)


def check_zero_recompile(out: dict):
    """Churn pin: NHWC + fused epilogues reach steady state at ONE
    compiled signature per site (no per-step recompiles)."""
    from deeplearning4j_tpu.analysis.churn import get_churn_detector
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.RandomState(3)
    x = rng.randn(8, 3, 16, 16).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.randint(0, 5, 8)]
    net = _optimized(build_fixture())
    ds = DataSet(x, y)
    det = get_churn_detector()
    for _ in range(6):
        net.fit(ds)
    sigs = det.signature_count("MultiLayerNetwork.fit", owner=net)
    assert sigs <= 1, f"fused/NHWC fit churned: {sigs} signatures"
    out["steady_state_signatures"] = sigs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    reps = 2 if args.quick else 3
    out = {"probe": "device_timing"}
    check_attribution(out, reps)
    check_fused_fp32(out, leaky=False)
    check_fused_fp32(out, leaky=True)      # the YOLO leaky-relu head
    check_fused_bf16(out, steps=4 if args.quick else 8)
    check_zero_recompile(out)
    out["ok"] = True
    print(json.dumps(out))


if __name__ == "__main__":
    main()
