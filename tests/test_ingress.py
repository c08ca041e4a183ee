"""Network front door (ISSUE 12): HTTP ingress, multi-model registry
with zero-drop hot-swap, and wire-level chaos.

The acceptance pins:

- **Hot-swap**: under sustained seeded load, rolling v1 -> v2 drops
  zero requests — every request resolves exactly once against exactly
  one version, steady-state recompiles stay 0 after the re-warm, and
  rollback restores v1 bit-identically.
- **Deadline propagation**: a wire ``deadline_ms`` that expires while
  queued is shed before dispatch and surfaces as 504 carrying the
  server-stamped latency.
- **Drain through the ingress**: SIGTERM with queued requests exits 0,
  the queued tail failing as retriable 503.
"""

import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import warnings
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.faults import ServingLoad, SwapSchedule
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import (DecodePreset, HttpIngress,
                                        ModelNotFoundError, ModelRegistry,
                                        ModelServer, ServingRequest)
from deeplearning4j_tpu.train import updaters

NIN, NOUT = 4, 3
REPO = Path(__file__).resolve().parents[1]


def mlp(seed=42):
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(updaters.Sgd(0.1)).list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=NOUT, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(NIN))
            .build())
    return MultiLayerNetwork(conf).init()


def feats(rows, seed=0):
    return np.random.RandomState(seed).randn(rows, NIN).astype(np.float32)


def post(url, path, body, headers=None, timeout=30.0):
    """POST returning (status, payload_dict, response_headers) — HTTP
    errors are outcomes here, not exceptions."""
    req = urllib.request.Request(f"{url}{path}", data=body,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def post_json(url, path, payload, headers=None, timeout=30.0):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    return post(url, path, json.dumps(payload).encode(), h, timeout)


def get(url, path, timeout=10.0):
    try:
        with urllib.request.urlopen(f"{url}{path}", timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class _SlowModel:
    def __init__(self, base, service_s):
        self.base = base
        self.service_s = service_s

    def output(self, x):
        time.sleep(self.service_s)
        return self.base.output(x)


@pytest.fixture()
def net():
    return mlp()


# =============================================================== wire basics
class TestWireBasics:
    def test_json_predict_roundtrip(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                x = feats(2)
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict",
                    {"instances": x.tolist()})
                assert code == 200
                assert payload["model"] == "m"
                assert payload["version"] == 1
                assert payload["latency_ms"] > 0
                np.testing.assert_allclose(
                    np.asarray(payload["predictions"], np.float32),
                    np.asarray(net.output(x)), rtol=1e-5)

    def test_raw_tensor_body(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                x = feats(3, seed=5)
                code, payload, _ = post(
                    ing.url, "/v1/models/m:predict", x.tobytes(),
                    {"Content-Type": "application/octet-stream",
                     "X-Tensor-Shape": "3,4",
                     "X-Tensor-Dtype": "float32"})
                assert code == 200
                np.testing.assert_allclose(
                    np.asarray(payload["predictions"], np.float32),
                    np.asarray(net.output(x)), rtol=1e-5)

    def test_raw_tensor_size_mismatch_is_400(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post(
                    ing.url, "/v1/models/m:predict", b"\x00" * 12,
                    {"Content-Type": "application/octet-stream",
                     "X-Tensor-Shape": "3,4"})
                assert code == 400 and "bytes" in payload["error"]

    def test_unknown_model_and_version_404(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/nope:predict",
                    {"instances": feats(1).tolist()})
                assert code == 404 and "not loaded" in payload["error"]
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict?version=9",
                    {"instances": feats(1).tolist()})
                assert code == 404

    def test_malformed_json_400(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post(
                    ing.url, "/v1/models/m:predict", b"not json",
                    {"Content-Type": "application/json"})
                assert code == 400
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict", {"rows": [[1]]})
                assert code == 400 and "instances" in payload["error"]

    def test_oversize_body_413(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            ing = HttpIngress(reg, port=0, max_body_mb=0.0001).start()
            try:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict",
                    {"instances": feats(8).tolist()})
                assert code == 413
            finally:
                ing.stop()

    def test_unknown_endpoints_404(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                assert get(ing.url, "/v2/whatever")[0] == 404
                assert post_json(ing.url, "/v1/models/m", {})[0] == 404

    def test_single_server_routes_as_default(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                x = feats(2)
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": x.tolist()})
                assert code == 200 and payload["version"] == 1
                assert post_json(ing.url, "/v1/models/other:predict",
                                 {"instances": x.tolist()})[0] == 404
                code, models = get(ing.url, "/v1/models")
                assert code == 200 and "default" in models["models"]
        finally:
            sv.close()

    def test_models_and_health_endpoints(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload = get(ing.url, "/v1/models")
                assert code == 200
                m = payload["models"]["m"]
                assert m["active"] == 1
                assert m["versions"]["1"]["ready"] is True
                code, payload = get(ing.url, "/v1/models/m")
                assert code == 200 and payload["model"] == "m"
                assert get(ing.url, "/v1/models/nope")[0] == 404
                assert get(ing.url, "/healthz")[0] == 200
                assert get(ing.url, "/readyz")[0] == 200


# ============================================================== image bodies
class TestImageBodies:
    H = W = 16

    @staticmethod
    def _jpeg_bytes(side, seed=0):
        from PIL import Image
        rng = np.random.RandomState(seed)
        arr = rng.randint(0, 255, (side, side, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        return buf.getvalue()

    def _pixel_model(self):
        # per-channel mean over pixels: a forward whose output is an
        # exact function of the decoded tensor, so the wire path pins
        # the decode itself
        return lambda x: jnp.mean(x, axis=(2, 3))

    def test_decode_preset_from_pipeline(self):
        from deeplearning4j_tpu.data.pipeline import ImagePipeline
        pipe = (ImagePipeline.list(files=["unused.jpg"])
                .decode(height=self.H, width=self.W, channels=3)
                .batch(1))
        preset = DecodePreset.from_pipeline(pipe)
        assert (preset.height, preset.width, preset.channels) == \
            (self.H, self.W, 3)
        arr = preset.decode(self._jpeg_bytes(self.H))
        assert arr.shape == (1, 3, self.H, self.W)
        assert arr.dtype == np.float32
        assert 0.0 <= arr.min() and arr.max() <= 255.0

    def test_raw_jpeg_body_predicts(self):
        preset = DecodePreset(self.H, self.W, 3)
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("pix", self._pixel_model(), decode=preset,
                     shapes=[(3, self.H, self.W)])
            with HttpIngress(reg, port=0) as ing:
                body = self._jpeg_bytes(32, seed=3)   # resized on decode
                code, payload, _ = post(
                    ing.url, "/v1/models/pix:predict", body,
                    {"Content-Type": "image/jpeg"})
                assert code == 200
                want = np.asarray(preset.decode(body)).mean(axis=(2, 3))
                np.testing.assert_allclose(
                    np.asarray(payload["predictions"], np.float32),
                    want, rtol=1e-4)

    def test_scaled_preset(self):
        preset = DecodePreset(self.H, self.W, 3, scale=1.0 / 255.0)
        arr = preset.decode(self._jpeg_bytes(self.H, seed=1))
        assert arr.max() <= 1.0

    def test_image_body_without_preset_is_415(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post(
                    ing.url, "/v1/models/m:predict",
                    self._jpeg_bytes(self.H),
                    {"Content-Type": "image/jpeg"})
                assert code == 415
                assert "decode preset" in payload["error"]


# ======================================================= deadline propagation
class TestDeadlineWire:
    def test_wire_deadline_expired_while_queued_is_504(self, net):
        """THE deadline pin: deadline_ms -> ServingRequest deadline; an
        expiry while queued sheds BEFORE dispatch and surfaces as 504
        with the server-stamped wait."""
        sv = ModelServer(_SlowModel(net, 0.15), batch_limit=1, max_queue=16,
                         coalesce_ms=0.0)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                # saturate the single-slot server so a queued request's
                # 30ms budget burns before dispatch
                blockers, threads = [], []
                for i in range(3):
                    t = threading.Thread(
                        target=lambda i=i: blockers.append(post_json(
                            ing.url, "/v1/models/default:predict",
                            {"instances": feats(1, seed=i).tolist()})))
                    t.start()
                    threads.append(t)
                time.sleep(0.03)
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1, seed=99).tolist()},
                    headers={"deadline_ms": "30"})
                for t in threads:
                    t.join(30.0)
                assert code == 504
                assert payload["type"] == "DeadlineExceededError"
                assert payload["retriable"] is False
                # server-stamped: at least the deadline elapsed, and the
                # stamp came from the server's own clock
                assert payload["latency_ms"] >= 30.0
                assert all(c == 200 for c, _, _ in blockers)
        finally:
            sv.close()

    def test_deadline_in_json_body(self, net):
        sv = ModelServer(_SlowModel(net, 0.15), batch_limit=1, max_queue=16,
                         coalesce_ms=0.0)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                done = []
                t = threading.Thread(target=lambda: done.append(post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()})))
                t.start()
                time.sleep(0.03)
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1, seed=9).tolist(),
                     "deadline_ms": 25})
                t.join(30.0)
                assert code == 504
        finally:
            sv.close()

    def test_bad_deadline_is_400(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()},
                    headers={"deadline_ms": "-5"})
                assert code == 400 and "deadline_ms" in payload["error"]
        finally:
            sv.close()

    def test_generous_deadline_completes(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()},
                    headers={"X-Deadline-Ms": "5000"})
                assert code == 200
        finally:
            sv.close()


# ========================================================= wire error taxonomy
class TestWireTaxonomy:
    def test_overload_is_429_with_retry_after(self, net):
        sv = ModelServer(_SlowModel(net, 0.2), batch_limit=1, max_queue=2,
                         coalesce_ms=0.0)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                results, threads = [], []
                for i in range(8):
                    t = threading.Thread(
                        target=lambda i=i: results.append(post_json(
                            ing.url, "/v1/models/default:predict",
                            {"instances": feats(1, seed=i).tolist()},
                            timeout=60)))
                    t.start()
                    threads.append(t)
                time.sleep(0.08)
                code, payload, hdrs = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1, seed=99).tolist()})
                for t in threads:
                    t.join(60.0)
                assert code == 429
                assert payload["type"] == "ServerOverloadedError"
                assert payload["retriable"] is True
                assert float(hdrs["Retry-After"]) > 0
        finally:
            sv.close()

    def test_draining_is_503_retriable(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                sv.drain()
                code, payload, hdrs = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()})
                assert code == 503
                assert payload["type"] == "ServerDrainingError"
                assert payload["retriable"] is True
                assert "Retry-After" in hdrs
                assert get(ing.url, "/readyz")[0] == 503
        finally:
            sv.close()

    def test_breaker_open_is_503_with_cooldown_retry_after(self, net):
        class Failing:
            def __init__(self):
                self.arm = False

            def output(self, x):
                if self.arm:
                    raise RuntimeError("injected dispatch failure")
                return net.output(x)

        model = Failing()
        sv = ModelServer(model, batch_limit=8, coalesce_ms=0.0,
                         breaker_threshold=1, breaker_cooldown=30.0,
                         max_retries=0)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                model.arm = True
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    code, payload, _ = post_json(
                        ing.url, "/v1/models/default:predict",
                        {"instances": feats(1).tolist()})
                assert code == 500      # the dispatch failure itself
                deadline = time.monotonic() + 5.0
                while sv.breaker.state != "open" \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
                code, payload, hdrs = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()})
                assert code == 503
                assert payload["type"] == "ServerUnhealthyError"
                assert payload["retriable"] is True
                # Retry-After carries the breaker's own cooldown hint
                assert 0 < float(hdrs["Retry-After"]) <= 30.0
                assert get(ing.url, "/healthz")[0] == 503
        finally:
            sv.close()

    def test_oversize_batch_is_400(self, net):
        sv = ModelServer(net, batch_limit=4, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(6).tolist()})
                assert code == 400 and "batch_limit" in payload["error"]
        finally:
            sv.close()


# ================================================================== hot-swap
class TestHotSwap:
    """THE zero-drop hot-swap acceptance pin."""

    def test_zero_drop_roll_under_sustained_load(self):
        net1, net2 = mlp(42), mlp(43)
        reg = ModelRegistry(batch_limit=8, max_queue=256, coalesce_ms=0.5)
        try:
            reg.load("m", net1, shapes=[(NIN,)])
            load = ServingLoad.seeded(11, mix="steady", n=120, rps=300.0,
                                      max_rows=2)
            handles = []

            def submit(x, deadline=None):
                h = reg.submit("m", x, deadline=deadline)
                handles.append(h)
                return h

            rolled = threading.Event()
            passes = []

            def sustain():      # traffic never stops until the roll has
                while True:     # landed, then one more pass on the new route
                    last = rolled.is_set()
                    passes.append(load.replay(submit, (NIN,),
                                              rng_seed=5 + len(passes)))
                    if last:
                        return

            replay = threading.Thread(target=sustain)
            replay.start()
            # v2 warms its whole ladder while v1 carries the load, then
            # the route rolls atomically mid-replay
            reg.load("m", net2)             # inherits v1's warm shapes
            prev = reg.roll("m")
            rolled.set()
            assert prev == 1
            replay.join(120.0)
            assert not replay.is_alive()
            assert len(handles) == len(load) * len(passes)

            # zero drops, exactly-once, exactly-one-version
            v1 = v2 = 0
            for h in handles:
                out = h.get(30.0)           # nothing errored
                assert h.resolutions == 1
                assert h.server in ("m:v1", "m:v2")
                if h.server == "m:v1":
                    v1 += 1
                else:
                    v2 += 1
                # the answer really came from the version that admitted
                # it: re-ask that version directly, pinned
                want = reg.server("m", 1 if h.server == "m:v1" else 2) \
                    .output(h.features, timeout=30.0)
                np.testing.assert_array_equal(out, want)
            assert v1 > 0 and v2 > 0, (v1, v2)

            # steady-state recompiles stayed 0 on BOTH versions
            assert reg.server("m", 1).recompiles_after_warmup() == 0
            assert reg.server("m", 2).recompiles_after_warmup() == 0
        finally:
            reg.close()

    def test_rollback_restores_v1_bit_identically(self):
        net1, net2 = mlp(42), mlp(43)
        x = feats(4, seed=21)
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net1, shapes=[(NIN,)])
            before = np.asarray(reg.output("m", x))
            reg.load("m", net2)
            reg.roll("m")
            rolled = np.asarray(reg.output("m", x))
            assert not np.array_equal(before, rolled)
            assert reg.server("m", 2).recompiles_after_warmup() == 0
            reg.rollback("m")
            after = np.asarray(reg.output("m", x))
            # SAME server object, SAME compiled programs: bitwise equal
            np.testing.assert_array_equal(before, after)
            assert reg.server("m", 1).recompiles_after_warmup() == 0

    def test_roll_does_not_drain_the_old_version(self, net):
        # requests queued on v1 when the roll lands must complete on v1
        reg = ModelRegistry(batch_limit=1, max_queue=32, coalesce_ms=0.0)
        try:
            reg.load("m", _SlowModel(net, 0.1), shapes=[(NIN,)])
            reqs = [reg.submit("m", feats(1, seed=i)) for i in range(5)]
            reg.load("m", net, shapes=[(NIN,)])
            reg.roll("m")
            post_roll = reg.submit("m", feats(1, seed=9))
            for r in reqs:
                r.get(30.0)
                assert r.server == "m:v1" and r.resolutions == 1
            post_roll.get(30.0)
            assert post_roll.server == "m:v2"
        finally:
            reg.close()

    def test_retire_waits_and_refuses_active(self, net):
        reg = ModelRegistry(batch_limit=8, coalesce_ms=0.5)
        try:
            reg.load("m", net, shapes=[(NIN,)])
            reg.load("m", net)
            with pytest.raises(ValueError, match="active"):
                reg.retire("m", 1)
            reg.roll("m")
            reg.retire("m", 1)
            with pytest.raises(ModelNotFoundError):
                reg.server("m", 1)
            with pytest.raises(ValueError, match="no previous"):
                reg.rollback("m")
        finally:
            reg.close()

    def test_swap_schedule_storm_over_the_wire(self):
        """Seeded swap-under-load chaos THROUGH the ingress: rolls and
        rollbacks land mid-replay over real sockets; every answered
        request carries a consistent version stamp and constant-output
        prediction, and none is dropped."""
        v1 = lambda x: jnp.full((x.shape[0], 1), 1.0)   # noqa: E731
        v2 = lambda x: jnp.full((x.shape[0], 1), 2.0)   # noqa: E731
        reg = ModelRegistry(batch_limit=8, max_queue=256, coalesce_ms=0.5)
        try:
            reg.load("c", v1, shapes=[(NIN,)])
            reg.load("c", v2)
            with HttpIngress(reg, port=0) as ing:
                load = ServingLoad.seeded(23, mix="steady", n=60,
                                          rps=150.0, max_rows=2)
                swaps = SwapSchedule.seeded(7, "c", load.duration(),
                                            n_swaps=3).start(reg)
                results = load.replay_http(ing.url, "c", (NIN,))
                performed = swaps.join(30.0)
            assert len(performed) == 3
            assert all(a in ("roll", "rollback") for _, _, a, _ in performed)
            assert len(results) == len(load)
            for spec, outcome in results:
                assert not isinstance(outcome, Exception), outcome
                code, payload = outcome
                assert code == 200
                val = np.asarray(payload["predictions"])[0, 0]
                ver = payload["version"]
                assert (val, ver) in ((1.0, 1), (2.0, 2)), (val, ver)
        finally:
            reg.close()


# ================================================================ wire chaos
class TestWireChaos:
    def test_slow_clients_do_not_block_fast_ones(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                load = ServingLoad.seeded(31, mix="steady", n=12, rps=100.0,
                                          max_rows=2, slow_frac=0.5,
                                          slow_client_seconds=0.3)
                assert any(s.slow_s > 0 for s in load)
                t0 = time.monotonic()
                chaos = threading.Thread(
                    target=lambda: load.replay_http(ing.url, "default",
                                                    (NIN,)))
                chaos.start()
                time.sleep(0.05)
                # a well-behaved client mid-storm answers promptly
                code, payload, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()})
                fast_latency = time.monotonic() - t0
                chaos.join(60.0)
                assert code == 200
                assert fast_latency < 2.0
        finally:
            sv.close()

    def test_mid_flight_disconnects_are_absorbed(self, net):
        from deeplearning4j_tpu import profiler as prof
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5)
        sv.warmup([(NIN,)])
        try:
            with HttpIngress(sv, port=0) as ing:
                before = prof.get_registry().get(
                    "dl4j_ingress_disconnects_total").value
                load = ServingLoad.seeded(37, mix="steady", n=16, rps=200.0,
                                          max_rows=2, disconnect_frac=0.4)
                n_disc = sum(1 for s in load if s.disconnect)
                assert n_disc > 0
                results = load.replay_http(ing.url, "default", (NIN,))
                disc = [o for _, o in results if o == "disconnected"]
                answered = [o for _, o in results
                            if isinstance(o, tuple)]
                assert len(disc) == n_disc
                assert all(code == 200 for code, _ in answered)
                # the server noticed and moved on; later traffic is fine
                deadline = time.monotonic() + 5.0
                while prof.get_registry().get(
                        "dl4j_ingress_disconnects_total").value < \
                        before + n_disc and time.monotonic() < deadline:
                    time.sleep(0.05)
                assert prof.get_registry().get(
                    "dl4j_ingress_disconnects_total").value >= \
                    before + n_disc
                code, _, _ = post_json(
                    ing.url, "/v1/models/default:predict",
                    {"instances": feats(1).tolist()})
                assert code == 200
        finally:
            sv.close()


# ============================================================== load endpoint
class TestLoadEndpoint:
    def test_v1_load_structure_and_gauges(self, net):
        from deeplearning4j_tpu import profiler as prof
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            reg.output("m", feats(2))
            with HttpIngress(reg, port=0) as ing:
                code, payload = get(ing.url, "/v1/load")
            assert code == 200
            m = payload["models"]["m"]
            assert m["version"] == 1
            assert m["queue_depth"] == 0
            assert m["breaker"] == "closed"
            assert m["shed_rate"] == 0.0
            assert m["batch_occupancy_mean"] is not None
            totals = payload["totals"]
            assert totals["ready"] is True
            assert totals["breakers_open"] == 0
            # the same hints exported as gauges
            g = prof.get_registry().get("dl4j_serving_shed_ratio")
            assert g.labels(server="m:v1").value == 0.0
            g = prof.get_registry().get("dl4j_serving_batch_occupancy_mean")
            assert g.labels(server="m:v1").value > 0


# ============================================================= registry lint
class TestRegistryRollLint:
    def test_w111_on_unwarmed_roll_target(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            reg.load("m", mlp(43), warm=False, shapes=None)
            report = reg.validate_roll("m")
            assert "DL4J-W111" in report.codes()
            with pytest.warns(UserWarning, match="W111"):
                reg.roll("m")

    def test_w111_on_missing_shapes(self):
        # dimension-agnostic forwards so both shapes genuinely warm
        fwd = lambda x: jnp.sum(x, axis=-1, keepdims=True)  # noqa: E731
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", fwd, shapes=[(NIN,), (NIN + 1,)])
            reg.load("m", fwd, shapes=[(NIN,)])
            report = reg.validate_roll("m")
            assert "DL4J-W111" in report.codes()

    def test_clean_roll_lints_clean(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            reg.load("m", mlp(43))
            assert reg.validate_roll("m").codes() == []

    def test_strict_roll_refuses_w111(self, net):
        from deeplearning4j_tpu.analysis import ModelValidationError
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            reg.load("m", mlp(43), warm=False)
            with pytest.raises(ModelValidationError):
                reg.roll("m", strict=True)
            assert reg.active_version("m") == 1

    def test_w111_documented(self):
        from deeplearning4j_tpu.analysis import DIAGNOSTIC_CODES
        assert "DL4J-W111" in DIAGNOSTIC_CODES


# ======================================================== drain through wire
class TestIngressDrain:
    def test_sigterm_through_ingress_exits_zero(self, tmp_path):
        """THE drain pin, through the wire: a real process serving HTTP
        takes SIGTERM under load; queued requests fail as retriable 503,
        in-flight work completes, exit code 0."""
        script = tmp_path / "ingress_sigterm.py"
        script.write_text(
            "import json, os, threading, time, urllib.error\n"
            "import urllib.request\n"
            "import numpy as np\n"
            "from deeplearning4j_tpu.nn import (InputType,\n"
            "    MultiLayerNetwork, NeuralNetConfiguration)\n"
            "from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer\n"
            "from deeplearning4j_tpu.serving import HttpIngress, ModelServer\n"
            "conf = (NeuralNetConfiguration.Builder().seed(0).list()\n"
            "        .layer(DenseLayer(nOut=8, activation='relu'))\n"
            "        .layer(OutputLayer(nOut=3, lossFunction='mcxent',\n"
            "                           activation='softmax'))\n"
            "        .setInputType(InputType.feedForward(4)).build())\n"
            "net = MultiLayerNetwork(conf).init()\n"
            "dispatched = threading.Event()\n"
            "class Slow:\n"
            "    def output(self, x):\n"
            "        if not warming:\n"
            "            dispatched.set()\n"
            "        time.sleep(0.1)\n"
            "        return net.output(x)\n"
            "warming = True\n"
            "sv = ModelServer(Slow(), batch_limit=1, max_queue=64,\n"
            "                 coalesce_ms=0.0, preemption=True)\n"
            "sv.warmup([(4,)])\n"
            "warming = False\n"
            "ing = HttpIngress(sv, port=0).start()\n"
            "body = json.dumps({'instances': [[0.0, 0.0, 0.0, 0.0]]})\\\n"
            "    .encode()\n"
            "results = []\n"
            "def one():\n"
            "    req = urllib.request.Request(\n"
            "        ing.url + '/v1/models/default:predict', data=body,\n"
            "        headers={'Content-Type': 'application/json'})\n"
            "    try:\n"
            "        with urllib.request.urlopen(req, timeout=60) as r:\n"
            "            results.append((r.status, json.loads(r.read())))\n"
            "    except urllib.error.HTTPError as e:\n"
            "        results.append((e.code, json.loads(e.read())))\n"
            "threads = [threading.Thread(target=one) for _ in range(16)]\n"
            "for t in threads:\n"
            "    t.start()\n"
            "# one dispatched and at least one queued behind it: the\n"
            "# state the pin is about, waited for and not slept for\n"
            "assert dispatched.wait(60)\n"
            "limit = time.monotonic() + 60\n"
            "while sv.queue_depth() < 1 and time.monotonic() < limit:\n"
            "    time.sleep(0.005)\n"
            "assert sv.queue_depth() >= 1\n"
            "os.kill(os.getpid(), 15)  # SIGTERM mid-load\n"
            "for t in threads:\n"
            "    t.join(90)\n"
            "codes = [c for c, _ in results]\n"
            "assert len(codes) == 16, codes\n"
            "ok = codes.count(200)\n"
            "drained = [p for c, p in results if c == 503]\n"
            "assert ok >= 1, codes\n"
            "assert drained, codes\n"
            "assert all(p['type'] == 'ServerDrainingError'\n"
            "           and p['retriable'] is True for p in drained)\n"
            "sv.close()\n"
            "ing.stop()\n"
            "print('DRAINED', ok, len(drained), flush=True)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
        proc = subprocess.run([sys.executable, str(script)],
                              capture_output=True, text=True, timeout=180,
                              env=env, cwd=str(REPO))
        assert proc.returncode == 0, proc.stderr
        assert "DRAINED" in proc.stdout


# ========================================================== request ownership
class TestRequestOwnership:
    def test_request_stamped_with_server(self, net):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=0.5, name="owner")
        sv.warmup([(NIN,)])
        try:
            r = sv.submit(feats(1))
            assert isinstance(r, ServingRequest)
            assert r.server == "owner"
            r.get(30.0)
        finally:
            sv.close()


# ======================================================= review-hardening pins
class TestReviewHardening:
    def test_oversize_refusal_closes_keepalive_connection(self, net):
        """A 413 that left the unread body on a persistent connection
        would desync the stream — the refusal must close it."""
        import http.client
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            ing = HttpIngress(reg, port=0, max_body_mb=0.0001).start()
            try:
                conn = http.client.HTTPConnection("127.0.0.1", ing.port,
                                                  timeout=10)
                body = json.dumps(
                    {"instances": feats(8).tolist()}).encode()
                conn.request("POST", "/v1/models/m:predict", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 413
                resp.read()
                assert resp.will_close   # Connection: close advertised
                conn.close()
            finally:
                ing.stop()

    def test_malformed_version_query_is_400(self, net):
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict?version=abc",
                    {"instances": feats(1).tolist()})
                assert code == 400 and "version" in payload["error"]

    def test_concurrent_loads_reserve_distinct_versions(self):
        """Two racing load()s of the same name must not pick the same
        version number while one warms outside the registry lock."""
        fwd = lambda x: jnp.tanh(x)                     # noqa: E731
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", fwd, shapes=[(NIN,)])
            got, errs = [], []

            def one():
                try:
                    got.append(reg.load("m", fwd, shapes=[(NIN,)],
                                        roll=False))
                except Exception as e:          # surfaced to the assert
                    errs.append(e)

            ts = [threading.Thread(target=one) for _ in range(2)]
            [t.start() for t in ts]
            [t.join(60.0) for t in ts]
            assert not errs, errs
            assert sorted(got) == [2, 3]
            assert reg.server("m", 2) is not reg.server("m", 3)

    def test_retire_timeout_never_fails_queued_requests(self, net):
        reg = ModelRegistry(batch_limit=1, max_queue=32, coalesce_ms=0.0)
        try:
            reg.load("m", _SlowModel(net, 0.15), shapes=[(NIN,)])
            reqs = [reg.submit("m", feats(1, seed=i)) for i in range(4)]
            reg.load("m", net, shapes=[(NIN,)])
            reg.roll("m")
            with pytest.raises(TimeoutError, match="still queued"):
                reg.retire("m", 1, timeout=0.05)
            # v1 kept serving: every queued request still completes
            for r in reqs:
                r.get(30.0)
                assert r.resolutions == 1
            reg.retire("m", 1, timeout=30.0)    # queue drained: clean
        finally:
            reg.close()
