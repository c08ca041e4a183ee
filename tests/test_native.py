"""Native C++ PJRT runtime tests (SURVEY.md §2.1 L0, §7 item 1).

The library builds from source in-test (g++ + the PJRT C API header — both
baked into the image). Execution tests need a PJRT C-API plugin named by
``DL4J_TPU_PJRT_PLUGIN``, else they skip (this image has no CPU plugin,
and a TPU plugin would need a chip). jax is used ONLY as a StableHLO
producer, pinned to CPU by tests/conftest.py, so the native client is the
sole owner of whatever device the plugin opens.
"""

import os
import shutil
import subprocess

import numpy as np
import pytest

from deeplearning4j_tpu.native import runtime as rt_mod
from deeplearning4j_tpu.native import (NativeRuntime, NativeRuntimeError,
                                       build_native_lib)


def test_native_lib_builds():
    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    path = build_native_lib()
    assert os.path.exists(path)
    # symbol table sanity: the flat C ABI is present
    out = subprocess.run(["nm", "-D", path], capture_output=True, text=True)
    for sym in ("dl4j_client_create", "dl4j_compile", "dl4j_execute",
                "dl4j_free_outputs", "dl4j_client_cache_stats"):
        assert sym in out.stdout


@pytest.fixture(scope="module")
def native_rt():
    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    plugin = os.environ.get(rt_mod.PLUGIN_ENV)
    if not plugin or not os.path.exists(plugin):
        pytest.skip(f"no PJRT plugin: {rt_mod.PLUGIN_ENV}={plugin!r}")
    try:
        rt = NativeRuntime.create()
    except NativeRuntimeError as e:   # plugin present but chip unclaimable
        pytest.skip(f"PJRT client unavailable: {e}")
    yield rt
    rt.close()


class TestNativeRuntime:
    def test_client_metadata(self, native_rt):
        assert native_rt.device_count >= 1
        assert native_rt.platform_name
        major, minor = native_rt.api_version
        assert (major, minor) >= (0, 40)

    def test_compile_and_execute_matmul(self, native_rt):
        import jax
        import jax.numpy as jnp

        def f(a, b):
            return a @ b + 1.0, jnp.tanh(a).sum()
        mlir = jax.jit(f).lower(jnp.zeros((4, 5), jnp.float32),
                                jnp.zeros((5, 3), jnp.float32)).as_text()
        exe = native_rt.compile(mlir)
        assert exe.num_outputs == 2
        rng = np.random.RandomState(0)
        a = rng.randn(4, 5).astype(np.float32)
        b = rng.randn(5, 3).astype(np.float32)
        outs = exe(a, b)
        np.testing.assert_allclose(outs[0], a @ b + 1.0, rtol=2e-2, atol=1e-2)
        np.testing.assert_allclose(outs[1], np.tanh(a).sum(), rtol=2e-2)

    def test_compile_cache_hits(self, native_rt):
        import jax
        import jax.numpy as jnp
        mlir = jax.jit(lambda x: x * 2.0).lower(
            jnp.zeros((3,), jnp.float32)).as_text()
        e1 = native_rt.compile(mlir)
        e2 = native_rt.compile(mlir)
        assert not e1.cache_hit and e2.cache_hit
        stats = native_rt.cache_stats()
        assert stats["hits"] >= 1 and stats["size"] >= 1
        out = e2(np.asarray([1.0, 2.0, 3.0], np.float32))
        np.testing.assert_allclose(out[0], [2.0, 4.0, 6.0], rtol=1e-3)

    def test_int_dtypes_roundtrip(self, native_rt):
        import jax
        mlir = jax.jit(lambda x: x + 1).lower(
            np.zeros((4,), np.int32)).as_text()
        exe = native_rt.compile(mlir)
        out = exe(np.asarray([1, 2, 3, 4], np.int32))
        np.testing.assert_array_equal(out[0], [2, 3, 4, 5])
        assert out[0].dtype == np.int32

    def test_compile_error_reported(self, native_rt):
        with pytest.raises(NativeRuntimeError, match="compile failed"):
            native_rt.compile("this is not mlir")


class TestNativeExecBackend:
    """backend="native" (VERDICT r4 #6): a SameDiff model's inference runs
    THROUGH the C++ runtime (trace -> StableHLO -> native client) and
    matches the jax path."""

    def test_samediff_mlp_through_native_client(self, native_rt):
        import jax.numpy as jnp
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        rng = np.random.RandomState(0)
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 6), dtype=np.float32)
        w1 = sd.var("w1", rng.randn(6, 8).astype(np.float32))
        b1 = sd.var("b1", np.zeros(8, np.float32))
        w2 = sd.var("w2", rng.randn(8, 3).astype(np.float32))
        h = sd.nn.relu(x.mmul(w1).add(b1))
        out = sd.nn.softmax(h.mmul(w2), name="probs")

        feeds = {"x": rng.randn(4, 6).astype(np.float32)}
        want = np.asarray(sd.output(feeds, ["probs"])["probs"])

        sd.setExecBackend("native")
        got = np.asarray(sd.output(feeds, ["probs"])["probs"])
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)

        # compiled-program cache: second call hits the native cache
        got2 = np.asarray(sd.output(feeds, ["probs"])["probs"])
        np.testing.assert_allclose(got2, got, rtol=1e-6)
        sd.setExecBackend("jax")

    def test_imported_zoo_model_native_parity(self, native_rt):
        """A LeNet-sized conv net through the native client."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        rng = np.random.RandomState(1)
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(2, 1, 12, 12), dtype=np.float32)
        w = sd.var("w", (rng.randn(4, 1, 3, 3) * 0.3).astype(np.float32))
        c = sd.cnn.conv2d(x, w, stride=(1, 1), pad=(0, 0))
        r = sd.nn.relu(c)
        p = sd.cnn.maxPooling2d(r, kernel=(2, 2), stride=(2, 2))
        out = sd.math.reduce_mean(p, name="m")
        feeds = {"x": rng.randn(2, 1, 12, 12).astype(np.float32)}
        want = np.asarray(sd.output(feeds, ["m"])["m"])
        sd.setExecBackend("native")
        got = np.asarray(sd.output(feeds, ["m"])["m"])
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)
