"""Layer tests (reference: unittests test_layers / per-layer tests)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def _x(*shape):
    rng = np.random.default_rng(3)
    return paddle.to_tensor(rng.standard_normal(shape).astype(np.float32))


class TestLinear:
    def test_forward(self):
        paddle.seed(0)  # the weights, not whatever ran before in the worker
        l = nn.Linear(8, 4)
        x = _x(2, 8)
        out = l(x)
        np.testing.assert_allclose(
            out.numpy(), x.numpy() @ l.weight.numpy() + l.bias.numpy(),
            rtol=1e-5)

    def test_no_bias(self):
        l = nn.Linear(8, 4, bias_attr=False)
        assert l.bias is None
        assert l(_x(2, 8)).shape == [2, 4]


class TestConvPool:
    def test_conv2d_shape(self):
        c = nn.Conv2D(3, 16, 3, stride=2, padding=1)
        assert c(_x(2, 3, 8, 8)).shape == [2, 16, 4, 4]

    def test_conv2d_vs_naive(self):
        c = nn.Conv2D(1, 1, 3, padding=0, bias_attr=False)
        x = _x(1, 1, 5, 5)
        out = c(x).numpy()
        w = c.weight.numpy()[0, 0]
        ref = np.zeros((3, 3), np.float32)
        xn = x.numpy()[0, 0]
        for i in range(3):
            for j in range(3):
                ref[i, j] = (xn[i:i + 3, j:j + 3] * w).sum()
        np.testing.assert_allclose(out[0, 0], ref, rtol=1e-4, atol=1e-5)

    def test_conv_grad(self):
        c = nn.Conv2D(2, 4, 3, padding=1)
        out = c(_x(2, 2, 6, 6))
        out.mean().backward()
        assert c.weight.grad is not None
        assert c.bias.grad is not None

    def test_conv2d_transpose(self):
        c = nn.Conv2DTranspose(4, 2, 3, stride=2, padding=1)
        assert c(_x(1, 4, 5, 5)).shape == [1, 2, 9, 9]

    def test_groups(self):
        c = nn.Conv2D(4, 8, 3, groups=2, padding=1)
        assert c(_x(1, 4, 6, 6)).shape == [1, 8, 6, 6]

    def test_pools(self):
        x = _x(1, 2, 8, 8)
        assert nn.MaxPool2D(2, 2)(x).shape == [1, 2, 4, 4]
        assert nn.AvgPool2D(2, 2)(x).shape == [1, 2, 4, 4]
        assert nn.AdaptiveAvgPool2D(1)(x).shape == [1, 2, 1, 1]
        np.testing.assert_allclose(
            nn.AdaptiveAvgPool2D(1)(x).numpy()[..., 0, 0],
            x.numpy().mean((2, 3)), rtol=1e-5)


class TestNorm:
    def test_layernorm(self):
        ln = nn.LayerNorm(16)
        x = _x(4, 16)
        out = ln(x).numpy()
        np.testing.assert_allclose(out.mean(-1), 0, atol=1e-5)
        np.testing.assert_allclose(out.std(-1), 1, atol=1e-2)

    def test_batchnorm_train_eval(self):
        bn = nn.BatchNorm2D(3)
        x = _x(4, 3, 5, 5)
        bn.train()
        out = bn(x)
        m = bn._mean.numpy().copy()
        assert not np.allclose(m, 0)  # running stats updated
        bn.eval()
        out2 = bn(x)
        assert out2.shape == out.shape

    def test_groupnorm(self):
        gn = nn.GroupNorm(2, 4)
        assert gn(_x(2, 4, 5, 5)).shape == [2, 4, 5, 5]

    def test_rmsnorm(self):
        rn = nn.RMSNorm(8)
        out = rn(_x(3, 8)).numpy()
        assert out.shape == (3, 8)


class TestEmbeddingDropout:
    def test_embedding(self):
        emb = nn.Embedding(10, 4)
        idx = paddle.to_tensor(np.array([[1, 2], [3, 4]], np.int64))
        out = emb(idx)
        np.testing.assert_allclose(out.numpy()[0, 0], emb.weight.numpy()[1])

    def test_embedding_grad_scatter(self):
        emb = nn.Embedding(10, 4)
        idx = paddle.to_tensor(np.array([1, 1, 2], np.int64))
        emb(idx).sum().backward()
        g = emb.weight.grad.numpy()
        np.testing.assert_allclose(g[1], np.full(4, 2.0))
        np.testing.assert_allclose(g[2], np.full(4, 1.0))
        np.testing.assert_allclose(g[0], np.zeros(4))

    def test_dropout_train_eval(self):
        paddle.seed(0)
        d = nn.Dropout(0.5)
        x = paddle.ones([1000])
        out = d(x)
        frac = (out.numpy() == 0).mean()
        assert 0.3 < frac < 0.7
        # upscale keeps expectation
        assert abs(out.numpy().mean() - 1.0) < 0.2
        d.eval()
        np.testing.assert_allclose(d(x).numpy(), x.numpy())


class TestActivationsLosses:
    def test_activations(self):
        x = _x(4, 4)
        np.testing.assert_allclose(nn.ReLU()(x).numpy(),
                                   np.maximum(x.numpy(), 0))
        np.testing.assert_allclose(
            F.sigmoid(x).numpy(), 1 / (1 + np.exp(-x.numpy())), rtol=1e-5)
        s = F.softmax(x).numpy()
        np.testing.assert_allclose(s.sum(-1), 1, rtol=1e-5)

    def test_cross_entropy(self):
        logits = _x(4, 5)
        label = paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))
        loss = F.cross_entropy(logits, label)
        lp = np.log(np.exp(logits.numpy()) /
                    np.exp(logits.numpy()).sum(-1, keepdims=True))
        ref = -lp[np.arange(4), [0, 1, 2, 3]].mean()
        np.testing.assert_allclose(float(loss), ref, rtol=1e-5)

    def test_cross_entropy_soft(self):
        logits = _x(4, 5)
        soft = paddle.nn.functional.softmax(_x(4, 5))
        loss = F.cross_entropy(logits, soft, soft_label=True)
        assert loss.shape == []

    def test_mse(self):
        a, b = _x(3, 3), _x(3, 3)
        np.testing.assert_allclose(
            float(F.mse_loss(a, b)), ((a.numpy() - b.numpy()) ** 2).mean(),
            rtol=1e-6)


class TestContainers:
    def test_sequential_layerlist(self):
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        assert len(m) == 3
        assert m(_x(2, 4)).shape == [2, 2]
        ll = nn.LayerList([nn.Linear(3, 3) for _ in range(4)])
        assert len(list(ll.parameters())) == 8

    def test_state_dict_roundtrip(self, tmp_path):
        m1 = nn.Sequential(nn.Linear(4, 8), nn.Linear(8, 2))
        m2 = nn.Sequential(nn.Linear(4, 8), nn.Linear(8, 2))
        paddle.save(m1.state_dict(), str(tmp_path / "m.pdparams"))
        sd = paddle.load(str(tmp_path / "m.pdparams"))
        m2.set_state_dict(sd)
        x = _x(2, 4)
        np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy(), rtol=1e-6)


class TestTransformer:
    def test_mha_shapes(self):
        mha = nn.MultiHeadAttention(32, 4)
        out = mha(_x(2, 6, 32))
        assert out.shape == [2, 6, 32]

    def test_mha_mask(self):
        mha = nn.MultiHeadAttention(16, 2)
        mask = paddle.to_tensor(np.tril(np.ones((6, 6))).astype(bool))
        out = mha(_x(1, 6, 16), attn_mask=mask.unsqueeze(0).unsqueeze(0))
        assert out.shape == [1, 6, 16]

    def test_encoder_grad(self):
        enc = nn.TransformerEncoder(nn.TransformerEncoderLayer(16, 2, 32), 2)
        out = enc(_x(2, 5, 16))
        out.mean().backward()
        grads = [p.grad for p in enc.parameters()]
        assert all(g is not None for g in grads)

    def test_decoder(self):
        model = nn.Transformer(d_model=16, nhead=2, num_encoder_layers=1,
                               num_decoder_layers=1, dim_feedforward=32)
        out = model(_x(2, 4, 16), _x(2, 6, 16))
        assert out.shape == [2, 6, 16]

    def test_mha_cache_incremental(self):
        mha = nn.MultiHeadAttention(16, 2)
        x = _x(1, 4, 16)
        cache = mha.gen_cache(x, type=nn.MultiHeadAttention.Cache)
        out1, cache = mha(x[:, :1], x[:, :1], x[:, :1], None, cache)
        assert cache.k.shape[1] == 1
        out2, cache = mha(x[:, 1:2], x[:, 1:2], x[:, 1:2], None, cache)
        assert cache.k.shape[1] == 2


class TestRNN:
    def test_lstm_shapes(self):
        lstm = nn.LSTM(4, 8, num_layers=2)
        out, (h, c) = lstm(_x(3, 5, 4))
        assert out.shape == [3, 5, 8]
        assert h.shape == [2, 3, 8]

    def test_gru_grad(self):
        gru = nn.GRU(4, 8)
        out, h = gru(_x(2, 6, 4))
        out.mean().backward()
        assert gru.weight_ih_l0.grad is not None

    def test_lstm_cell(self):
        cell = nn.LSTMCell(4, 8)
        h, (hn, cn) = cell(_x(2, 4))
        assert h.shape == [2, 8]


class TestMemoryEfficientAttention:
    """Reference incubate/nn/memory_efficient_attention.py — same O(T)
    algorithm as flash attention, dispatched to the framework kernel."""

    def test_causal_matches_dense_reference(self):
        from paddle_tpu.incubate.nn import (LowerTriangularMask,
                                            memory_efficient_attention)

        rng = np.random.default_rng(0)
        B, T, N, H = 2, 16, 2, 8
        q = rng.normal(size=(B, T, N, H)).astype(np.float32)
        k = rng.normal(size=(B, T, N, H)).astype(np.float32)
        v = rng.normal(size=(B, T, N, H)).astype(np.float32)
        out = memory_efficient_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            attn_bias=LowerTriangularMask()).numpy()

        # dense reference
        logits = np.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(H)
        tri = np.tril(np.ones((T, T), bool))
        logits = np.where(tri, logits, -np.inf)
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        ref = np.einsum("bnqk,bknh->bqnh", probs, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)

    def test_tensor_bias_and_identity_loss(self):
        from paddle_tpu.incubate.nn import (identity_loss,
                                            memory_efficient_attention)

        rng = np.random.default_rng(1)
        B, T, N, H = 1, 8, 2, 4
        q = rng.normal(size=(B, T, N, H)).astype(np.float32)
        bias = rng.normal(size=(B, N, T, T)).astype(np.float32)
        out = memory_efficient_attention(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
            attn_bias=paddle.to_tensor(bias))
        assert out.shape == [B, T, N, H]
        assert np.isfinite(out.numpy()).all()
        x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        assert float(identity_loss(x, "sum")) == 6.0
        assert float(identity_loss(x, "mean")) == 2.0
        np.testing.assert_allclose(identity_loss(x, "none").numpy(),
                                   [1, 2, 3])

    def test_memory_efficient_attention_has_grads(self):
        from paddle_tpu.incubate.nn import (LowerTriangularMask,
                                            memory_efficient_attention)

        rng = np.random.default_rng(2)
        q = paddle.to_tensor(rng.normal(size=(1, 8, 2, 4))
                             .astype(np.float32))
        q.stop_gradient = False
        out = memory_efficient_attention(q, q, q,
                                         attn_bias=LowerTriangularMask())
        out.sum().backward()
        assert q.grad is not None
        assert np.isfinite(np.asarray(q.grad.numpy())).all()
