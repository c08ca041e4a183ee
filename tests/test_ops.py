"""Op-layer tests.

Reference test-strategy parity (SURVEY.md §4): golden-value conformance —
conv/pool/rnn ops are checked against torch (CPU) goldens the way the
reference pins op semantics to TF via TFGraphTestAllSameDiff; plus
finite-difference gradient checks as the universal backstop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick
import torch
import torch.nn.functional as F

from deeplearning4j_tpu.ops import convolution as conv
from deeplearning4j_tpu.ops import losses, normalization, recurrent, registry
from deeplearning4j_tpu.ops import attention as attn


def t2j(t):
    return jnp.asarray(t.detach().numpy())


class TestConvGolden:
    def test_conv2d_vs_torch(self):
        rng = np.random.RandomState(0)
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        w = rng.randn(4, 3, 3, 3).astype(np.float32)
        b = rng.randn(4).astype(np.float32)
        want = F.conv2d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                        stride=2, padding=1).numpy()
        got = conv.conv2d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                          stride=2, pad=1)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_conv2d_dilated_vs_torch(self):
        rng = np.random.RandomState(1)
        x = rng.randn(1, 2, 10, 10).astype(np.float32)
        w = rng.randn(3, 2, 3, 3).astype(np.float32)
        want = F.conv2d(torch.tensor(x), torch.tensor(w), dilation=2).numpy()
        got = conv.conv2d(jnp.asarray(x), jnp.asarray(w), dilation=2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_conv2d_groups_vs_torch(self):
        rng = np.random.RandomState(2)
        x = rng.randn(2, 4, 6, 6).astype(np.float32)
        w = rng.randn(8, 2, 3, 3).astype(np.float32)
        want = F.conv2d(torch.tensor(x), torch.tensor(w), groups=2).numpy()
        got = conv.conv2d(jnp.asarray(x), jnp.asarray(w), groups=2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_depthwise_vs_torch(self):
        rng = np.random.RandomState(3)
        x = rng.randn(2, 3, 7, 7).astype(np.float32)
        # torch depthwise: weight [3*2, 1, k, k] groups=3; ours [mult=2, 3, k, k]
        w_ours = rng.randn(2, 3, 3, 3).astype(np.float32)
        w_torch = w_ours.transpose(1, 0, 2, 3).reshape(6, 1, 3, 3)
        want = F.conv2d(torch.tensor(x), torch.tensor(w_torch), groups=3).numpy()
        got = conv.depthwise_conv2d(jnp.asarray(x), jnp.asarray(w_ours))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_deconv2d_vs_torch(self):
        rng = np.random.RandomState(4)
        x = rng.randn(1, 3, 5, 5).astype(np.float32)
        w_ours = rng.randn(4, 3, 3, 3).astype(np.float32)  # [outC,inC,kH,kW]
        # torch convtranspose weight layout: [inC, outC, kH, kW]
        w_torch = w_ours.transpose(1, 0, 2, 3)
        want = F.conv_transpose2d(torch.tensor(x), torch.tensor(w_torch), stride=2).numpy()
        got = conv.deconv2d(jnp.asarray(x), jnp.asarray(w_ours), stride=2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_maxpool_vs_torch(self):
        rng = np.random.RandomState(5)
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        want = F.max_pool2d(torch.tensor(x), 2, 2).numpy()
        got = conv.maxpool2d(jnp.asarray(x), kernel=2, stride=2)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_avgpool_vs_torch(self):
        rng = np.random.RandomState(6)
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        want = F.avg_pool2d(torch.tensor(x), 3, 2).numpy()
        got = conv.avgpool2d(jnp.asarray(x), kernel=3, stride=2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_conv1d_causal_keeps_length(self):
        x = jnp.ones((2, 4, 10))
        w = jnp.ones((8, 4, 3))
        out = conv.conv1d(x, w, mode="causal")
        assert out.shape == (2, 8, 10)

    def test_same_padding_shape(self):
        x = jnp.ones((1, 3, 9, 9))
        w = jnp.ones((5, 3, 3, 3))
        out = conv.conv2d(x, w, stride=2, mode="same")
        assert out.shape == (1, 5, 5, 5)

    def test_space_depth_roundtrip(self):
        x = jnp.arange(2 * 4 * 4 * 4.0).reshape(2, 4, 4, 4)
        y = conv.space_to_depth(x, 2)
        z = conv.depth_to_space(y, 2)
        np.testing.assert_allclose(z, x)

    def test_upsampling(self):
        x = jnp.arange(4.0).reshape(1, 1, 2, 2)
        y = conv.upsampling2d(x, 2)
        assert y.shape == (1, 1, 4, 4)
        np.testing.assert_allclose(y[0, 0, :2, :2], jnp.full((2, 2), x[0, 0, 0, 0]))


class TestRecurrentGolden:
    def test_lstm_vs_torch(self):
        rng = np.random.RandomState(7)
        T, N, C, H = 5, 3, 4, 6
        x = rng.randn(T, N, C).astype(np.float32)
        m = torch.nn.LSTM(C, H)
        # torch gate order: i, f, g, o — same as ours
        w_ih = m.weight_ih_l0.detach().numpy().T  # [C, 4H]
        w_hh = m.weight_hh_l0.detach().numpy().T
        b = (m.bias_ih_l0 + m.bias_hh_l0).detach().numpy()
        want, (hT, cT) = m(torch.tensor(x))
        outs, (h, c) = recurrent.lstm(jnp.asarray(x), jnp.asarray(w_ih),
                                      jnp.asarray(w_hh), jnp.asarray(b))
        np.testing.assert_allclose(outs, want.detach().numpy(), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(h, hT[0].detach().numpy(), rtol=1e-4, atol=1e-5)

    def test_gru_vs_torch(self):
        rng = np.random.RandomState(8)
        T, N, C, H = 4, 2, 3, 5
        x = rng.randn(T, N, C).astype(np.float32)
        m = torch.nn.GRU(C, H)
        w_ih = m.weight_ih_l0.detach().numpy().T
        w_hh = m.weight_hh_l0.detach().numpy().T
        b_ih = m.bias_ih_l0.detach().numpy()
        b_hh = m.bias_hh_l0.detach().numpy()
        want, hT = m(torch.tensor(x))
        outs, h = recurrent.gru(jnp.asarray(x), jnp.asarray(w_ih),
                                jnp.asarray(w_hh), jnp.asarray(b_ih), jnp.asarray(b_hh))
        np.testing.assert_allclose(outs, want.detach().numpy(), rtol=1e-4, atol=1e-5)

    def test_lstm_mask_freezes_state(self):
        T, N, C, H = 6, 2, 3, 4
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(T, N, C).astype(np.float32))
        w_ih = jnp.asarray(rng.randn(C, 4 * H).astype(np.float32) * 0.1)
        w_hh = jnp.asarray(rng.randn(H, 4 * H).astype(np.float32) * 0.1)
        b = jnp.zeros((4 * H,), jnp.float32)
        mask = jnp.asarray(np.array([[1, 1], [1, 1], [1, 0], [1, 0], [1, 0], [1, 0]], np.float32))
        outs, (h, c) = recurrent.lstm(x, w_ih, w_hh, b, mask_tn=mask)
        # example 1 masked from t=2: outputs zero, state frozen at t=1
        np.testing.assert_allclose(outs[2:, 1], np.zeros((4, H)), atol=1e-7)
        outs_short, (h_s, _) = recurrent.lstm(x[:2, 1:2], w_ih, w_hh, b)
        np.testing.assert_allclose(h[1], h_s[0], rtol=1e-5, atol=1e-6)


class TestNorm:
    def test_batchnorm_vs_torch(self):
        rng = np.random.RandomState(10)
        x = rng.randn(4, 3, 5, 5).astype(np.float32)
        g = rng.rand(3).astype(np.float32) + 0.5
        b = rng.randn(3).astype(np.float32)
        mean = rng.randn(3).astype(np.float32)
        var = rng.rand(3).astype(np.float32) + 0.5
        want = F.batch_norm(torch.tensor(x), torch.tensor(mean), torch.tensor(var),
                            torch.tensor(g), torch.tensor(b), eps=1e-5).numpy()
        got = normalization.batch_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
                                       jnp.asarray(mean), jnp.asarray(var))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_layernorm_vs_torch(self):
        rng = np.random.RandomState(11)
        x = rng.randn(4, 7).astype(np.float32)
        g = rng.rand(7).astype(np.float32)
        b = rng.randn(7).astype(np.float32)
        want = F.layer_norm(torch.tensor(x), (7,), torch.tensor(g), torch.tensor(b)).numpy()
        got = normalization.layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_lrn_vs_torch(self):
        rng = np.random.RandomState(12)
        x = rng.randn(2, 8, 4, 4).astype(np.float32)
        want = F.local_response_norm(torch.tensor(x), 5, alpha=1e-4, beta=0.75, k=1.0).numpy()
        # torch divides alpha by n; ours uses raw alpha like TF/DL4J
        got = normalization.lrn(jnp.asarray(x), depth=5, alpha=1e-4 / 5, beta=0.75, bias=1.0)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_dropout_scales(self):
        x = jnp.ones((1000,))
        out = normalization.dropout(x, 0.5, jax.random.PRNGKey(0))
        assert abs(float(jnp.mean(out)) - 1.0) < 0.1
        np.testing.assert_allclose(normalization.dropout(x, 0.5, jax.random.PRNGKey(0), train=False), x)


class TestAttention:
    def test_mha_vs_torch(self):
        rng = np.random.RandomState(13)
        B, T, E, H = 2, 5, 8, 2
        x = rng.randn(B, T, E).astype(np.float32)
        wq, wk, wv, wo = (rng.randn(E, E).astype(np.float32) * 0.2 for _ in range(4))
        m = torch.nn.MultiheadAttention(E, H, bias=False, batch_first=True)
        with torch.no_grad():
            m.in_proj_weight.copy_(torch.tensor(np.concatenate([wq.T, wk.T, wv.T])))
            m.out_proj.weight.copy_(torch.tensor(wo.T))
        want, _ = m(torch.tensor(x), torch.tensor(x), torch.tensor(x))
        got = attn.multi_head_attention(jnp.asarray(x), jnp.asarray(x),
                                        jnp.asarray(wq), jnp.asarray(wk),
                                        jnp.asarray(wv), jnp.asarray(wo), num_heads=H)
        np.testing.assert_allclose(got, want.detach().numpy(), rtol=1e-4, atol=1e-5)

    def test_flash_matches_exact(self):
        rng = np.random.RandomState(14)
        B, T, H, D = 2, 33, 2, 4
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        exact = attn.dot_product_attention(q, k, v)
        flash = attn.flash_attention(q, k, v, block_size=8)
        np.testing.assert_allclose(flash, exact, rtol=1e-4, atol=1e-5)

    def test_flash_causal_matches_exact(self):
        rng = np.random.RandomState(15)
        B, T, H, D = 1, 17, 1, 4
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        exact = attn.dot_product_attention(q, k, v, is_causal=True)
        flash = attn.flash_attention(q, k, v, is_causal=True, block_size=5)
        np.testing.assert_allclose(flash, exact, rtol=1e-4, atol=1e-5)


class TestLosses:
    def test_mse_matches_torch(self):
        rng = np.random.RandomState(16)
        y = rng.randn(4, 3).astype(np.float32)
        p = rng.randn(4, 3).astype(np.float32)
        want = F.mse_loss(torch.tensor(p), torch.tensor(y)).numpy()
        got = losses.mse(jnp.asarray(y), jnp.asarray(p))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_softmax_xent_matches_torch(self):
        rng = np.random.RandomState(17)
        logits = rng.randn(5, 4).astype(np.float32)
        labels = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 5)]
        want = F.cross_entropy(torch.tensor(logits), torch.tensor(labels.argmax(1))).numpy()
        got = losses.softmax_cross_entropy_logits(jnp.asarray(labels), jnp.asarray(logits))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        got_sparse = losses.sparse_mcxent(jnp.asarray(labels.argmax(1)), jnp.asarray(logits))
        np.testing.assert_allclose(got_sparse, want, rtol=1e-5)

    def test_xent_binary(self):
        y = jnp.asarray([[1.0], [0.0]])
        p = jnp.asarray([[0.9], [0.2]])
        want = float(F.binary_cross_entropy(torch.tensor([[0.9], [0.2]]), torch.tensor([[1.0], [0.0]])))
        got = float(losses.xent(y, p))
        assert abs(got - want) < 1e-5

    def test_masked_loss_ignores_masked(self):
        y = jnp.asarray([[1.0, 0.0], [0.5, 0.5]])
        p = jnp.asarray([[0.8, 0.2], [0.0, 1.0]])
        mask = jnp.asarray([1.0, 0.0])
        got = losses.mse(y, p, mask=mask)
        want = losses.mse(y[:1], p[:1])
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_loss_gradcheck(self):
        """Finite-difference check through the loss in fp64, like the
        reference's GradCheckUtil (SURVEY §4 centerpiece)."""
        with jax.enable_x64(True):
            rng = np.random.RandomState(18)
            logits = jnp.asarray(rng.randn(3, 4))
            labels = jnp.asarray(np.eye(4)[rng.randint(0, 4, 3)])
            f = lambda lg: losses.softmax_cross_entropy_logits(labels, lg)
            g = jax.grad(f)(logits)
            eps = 1e-6
            for i in range(3):
                for j in range(4):
                    lp = logits.at[i, j].add(eps)
                    lm = logits.at[i, j].add(-eps)
                    fd = (f(lp) - f(lm)) / (2 * eps)
                    np.testing.assert_allclose(g[i, j], fd, rtol=1e-4, atol=1e-7)


class TestRegistry:
    def test_registry_size_and_dispatch(self):
        assert len(registry.all_ops()) > 200
        out = registry.exec_op("add", jnp.ones(3), jnp.ones(3))
        np.testing.assert_allclose(out, 2 * np.ones(3))

    def test_platform_override(self):
        calls = []
        orig = registry.get("relu")
        registry.register_platform_override("relu", lambda x: calls.append(1) or orig(x))
        try:
            registry.exec_op("relu", jnp.asarray([-1.0, 1.0]))
            assert calls == [1]
        finally:
            registry.clear_platform_override("relu")

    def test_nms(self):
        boxes = jnp.asarray([[0, 0, 10, 10], [0, 0, 9, 9], [20, 20, 30, 30]], jnp.float32)
        scores = jnp.asarray([0.9, 0.8, 0.7])
        keep = registry.exec_op("non_max_suppression", boxes, scores, 3, 0.5)
        assert list(np.asarray(keep)) == [0, 2, -1]

    def test_sequence_mask(self):
        m = registry.exec_op("sequence_mask", jnp.asarray([1, 3]), 4)
        np.testing.assert_array_equal(np.asarray(m), [[1, 0, 0, 0], [1, 1, 1, 0]])

    def test_cumsum_exclusive_reverse(self):
        x = jnp.asarray([1.0, 2.0, 3.0])
        np.testing.assert_allclose(registry.exec_op("cumsum", x, 0, True, False), [0, 1, 3])
        np.testing.assert_allclose(registry.exec_op("cumsum", x, 0, False, True), [6, 5, 3])
