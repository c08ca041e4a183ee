"""Pallas platform-override tests (ref: the PlatformHelper dispatch tests
of libnd4j's mkldnn/cudnn helpers — same contract: the override must be
numerically interchangeable with the generic op, and unsupported shapes
must fall back). Kernels run via the Pallas interpreter on the CPU suite;
the same code compiles for TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import registry


@pytest.fixture
def overrides():
    pk.install_platform_overrides(interpret=True)
    yield
    pk.uninstall_platform_overrides()


class TestLayerNormKernel:
    def test_matches_generic(self):
        rng = np.random.RandomState(0)
        x = rng.randn(16, 256).astype(np.float32) * 3 + 1
        g = rng.rand(256).astype(np.float32) + 0.5
        b = rng.randn(256).astype(np.float32)
        ln = pk.make_layer_norm_override(interpret=True)
        from deeplearning4j_tpu.ops import normalization as norm_ops
        got = np.asarray(ln(x, g, b))
        want = np.asarray(norm_ops.layer_norm(x, g, b))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_gradients_flow(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        g = jnp.asarray(rng.rand(128).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(128).astype(np.float32))
        ln = pk.make_layer_norm_override(interpret=True)
        from deeplearning4j_tpu.ops import normalization as norm_ops

        def loss_pallas(x, g, b):
            return jnp.sum(jnp.square(ln(x, g, b)))

        def loss_generic(x, g, b):
            return jnp.sum(jnp.square(norm_ops.layer_norm(x, g, b)))

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, g, b)
        gg = jax.grad(loss_generic, argnums=(0, 1, 2))(x, g, b)
        for a, bb in zip(gp, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-4, atol=1e-4)

    def test_unsupported_shape_falls_back(self):
        rng = np.random.RandomState(2)
        ln = pk.make_layer_norm_override(interpret=True)
        # lane dim 100 is not a multiple of 128: must use generic path
        x = rng.randn(8, 100).astype(np.float32)
        g = np.ones(100, np.float32)
        b = np.zeros(100, np.float32)
        from deeplearning4j_tpu.ops import normalization as norm_ops
        np.testing.assert_allclose(np.asarray(ln(x, g, b)),
                                   np.asarray(norm_ops.layer_norm(x, g, b)),
                                   rtol=1e-5, atol=1e-5)


class TestSoftmaxKernel:
    def test_matches_jax(self):
        rng = np.random.RandomState(3)
        x = rng.randn(32, 128).astype(np.float32) * 5
        sm = pk.make_softmax_override(interpret=True)
        np.testing.assert_allclose(np.asarray(sm(x)),
                                   np.asarray(jax.nn.softmax(x, axis=-1)),
                                   rtol=1e-5, atol=1e-6)

    def test_gradient_matches(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        sm = pk.make_softmax_override(interpret=True)
        gp = jax.grad(lambda v: jnp.sum(sm(v) ** 2))(x)
        gg = jax.grad(lambda v: jnp.sum(jax.nn.softmax(v, -1) ** 2))(x)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gg),
                                   rtol=1e-4, atol=1e-5)


class TestPlatformDispatch:
    def test_override_shadows_generic(self, overrides):
        rng = np.random.RandomState(5)
        x = rng.randn(8, 128).astype(np.float32)
        got = np.asarray(registry.exec_op("softmax", x))
        np.testing.assert_allclose(got, np.asarray(jax.nn.softmax(x, -1)),
                                   rtol=1e-5, atol=1e-6)
        # the override IS what the registry resolves
        assert registry.get("softmax").__name__ == "softmax"
        assert registry.get("softmax") is not registry._REGISTRY["softmax"]

    def test_uninstall_restores_generic(self):
        pk.install_platform_overrides(interpret=True)
        pk.uninstall_platform_overrides()
        assert registry.get("softmax") is registry._REGISTRY["softmax"]

    def test_install_registers_the_three_kernels_and_no_other(self):
        """The conv path's ``scale_shift_act`` epilogue has no kernel (PR
        27): it stays the generic op whatever is installed."""
        assert not registry._PLATFORM_OVERRIDES
        pk.install_platform_overrides(interpret=True)
        try:
            assert set(registry._PLATFORM_OVERRIDES) == {
                "layer_norm", "softmax", "flash_attention"}
            assert registry.get("scale_shift_act") \
                is registry._REGISTRY["scale_shift_act"]
        finally:
            pk.uninstall_platform_overrides()
        assert not registry._PLATFORM_OVERRIDES

    def test_samediff_graph_uses_override(self, overrides):
        """A SameDiff graph records registry ops by name — the platform
        override applies when the graph executes."""
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        rng = np.random.RandomState(6)
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(8, 128), dtype=np.float32)
        y = x.mul(2.0)
        out = sd._record("softmax", [y.name])
        xv = rng.randn(8, 128).astype(np.float32)
        got = np.asarray(sd.output({"x": xv}, [out.name])[out.name])
        np.testing.assert_allclose(got, np.asarray(jax.nn.softmax(xv * 2, -1)),
                                   rtol=1e-5, atol=1e-6)


class TestFlashAttentionKernel:
    """Pallas fused flash attention (VERDICT r4 #5): forward and custom
    backward must match exact einsum attention."""

    def _qkv(self, B=2, T=256, H=2, D=64, dtype=jnp.float32, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32),
                                 dtype)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_exact(self, causal):
        from deeplearning4j_tpu.ops import attention as attn_ops
        from deeplearning4j_tpu.ops.pallas_kernels import \
            make_flash_attention_override
        q, k, v = self._qkv()
        fa = make_flash_attention_override(interpret=True, bq=128, bk=128)
        got = np.asarray(fa(q, k, v, is_causal=causal))
        want = np.asarray(attn_ops.dot_product_attention(
            q, k, v, is_causal=causal))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_exact(self, causal):
        from deeplearning4j_tpu.ops import attention as attn_ops
        from deeplearning4j_tpu.ops.pallas_kernels import \
            make_flash_attention_override
        q, k, v = self._qkv(T=128)
        fa = make_flash_attention_override(interpret=True, bq=128, bk=128)

        def loss_fa(q, k, v):
            return jnp.sum(jnp.sin(fa(q, k, v, is_causal=causal)))

        def loss_exact(q, k, v):
            return jnp.sum(jnp.sin(attn_ops.dot_product_attention(
                q, k, v, is_causal=causal)))

        g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_exact, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_masked_and_odd_shapes_fall_back(self):
        from deeplearning4j_tpu.ops.pallas_kernels import \
            make_flash_attention_override
        from deeplearning4j_tpu.ops import attention as attn_ops
        fa = make_flash_attention_override(interpret=True, bq=128, bk=128)
        rng = np.random.RandomState(1)
        # odd T (not block-divisible) and a mask both route to the scan path
        q = jnp.asarray(rng.randn(1, 100, 2, 64), jnp.float32)
        mask = jnp.ones((1, 1, 100, 100))
        got = np.asarray(fa(q, q, q, mask=mask))
        want = np.asarray(attn_ops.dot_product_attention(q, q, q, mask=mask))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_dispatch_through_flash_attention_entry(self):
        """attention.flash_attention routes through the installed override."""
        from deeplearning4j_tpu.ops import attention as attn_ops
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        q, k, v = self._qkv(T=128)
        pk.install_platform_overrides(interpret=True)
        try:
            got = np.asarray(attn_ops.flash_attention(q, k, v))
        finally:
            pk.uninstall_platform_overrides()
        want = np.asarray(attn_ops.dot_product_attention(q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
