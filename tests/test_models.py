"""Model-zoo tests: GPT / BERT / ERNIE / ResNet + jit save/load + MoE."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _ids(rng, v, shape):
    return paddle.to_tensor(rng.integers(0, v, shape).astype(np.int64))


class TestGPT:
    def test_forward_backward(self):
        from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny

        rng = np.random.default_rng(0)
        m = gpt_tiny(vocab_size=128)
        toks = _ids(rng, 128, (2, 16))
        logits = m(toks)
        assert logits.shape == [2, 16, 128]
        loss = GPTPretrainingCriterion()(logits, toks)
        loss.backward()
        assert all(p.grad is not None for p in m.parameters())

    def test_train_step_converges(self):
        from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny

        paddle.seed(7)
        rng = np.random.default_rng(7)
        m = gpt_tiny(vocab_size=64)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())

        def step(toks, labels):
            loss = crit(m(toks), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        train = paddle.jit.TrainStep(step, m, opt)
        toks = _ids(rng, 64, (2, 16))
        labels = paddle.to_tensor(np.roll(toks.numpy(), -1, 1))
        losses = [float(train(toks, labels)) for _ in range(10)]
        assert losses[-1] < losses[0]

    def test_kv_cache_decode(self):
        """Incremental equals full, through the one cache form the model
        has: paged pools (ops/kv_pool.py) under an identity block table,
        a 5-token prefill and then a token a step."""
        import jax.numpy as jnp

        from paddle_tpu.models import gpt_tiny

        rng = np.random.default_rng(1)
        m = gpt_tiny(vocab_size=64)
        m.eval()
        toks = _ids(rng, 64, (1, 8))
        bs, M = 4, 2
        ks, vs = m.gpt.kv_cache_spec().allocate(1 + M, bs, jnp.float32)
        caches = [(paddle.Tensor(k), paddle.Tensor(v))
                  for k, v in zip(ks, vs)]
        table = paddle.to_tensor(np.arange(1, 1 + M, dtype=np.int32)[None])
        w = m.gpt.embeddings.word_embeddings.weight
        with paddle.no_grad():
            full = m(toks)
            outs = []
            for lo, hi in [(0, 5), (5, 6), (6, 7), (7, 8)]:
                pos = paddle.to_tensor(np.arange(lo, hi, dtype=np.int64)[None])
                x, caches = m.gpt(
                    toks[:, lo:hi], position_ids=pos, caches=caches,
                    cache_offsets=paddle.to_tensor(np.array([lo], np.int32)),
                    seq_lens=paddle.to_tensor(np.array([hi], np.int32)),
                    block_tables=table)
                outs.append(paddle.matmul(x, w, transpose_y=True))
            inc = paddle.concat(outs, axis=1)
        np.testing.assert_allclose(full.numpy(), inc.numpy(), rtol=2e-2,
                                   atol=2e-3)


class TestBert:
    def test_pretrain_heads(self):
        from paddle_tpu.models import (BertPretrainingCriterion,
                                       bert_tiny)
        from paddle_tpu.models.bert import BertForPretraining

        rng = np.random.default_rng(0)
        bert = bert_tiny(vocab_size=256, max_position_embeddings=64)
        m = BertForPretraining(bert)
        ids = _ids(rng, 256, (2, 16))
        mask = paddle.ones([2, 16], "int64")
        scores, nsp = m(ids, attention_mask=mask)
        assert scores.shape == [2, 16, 256]
        assert nsp.shape == [2, 2]
        crit = BertPretrainingCriterion(256)
        loss = crit(scores, nsp, ids, paddle.to_tensor(
            np.zeros((2, 1), np.int64)))
        loss.backward()
        assert bert.embeddings.word_embeddings.weight.grad is not None

    def test_sequence_classification(self):
        from paddle_tpu.models import bert_tiny
        from paddle_tpu.models.bert import BertForSequenceClassification

        rng = np.random.default_rng(0)
        m = BertForSequenceClassification(
            bert_tiny(vocab_size=128, max_position_embeddings=32), 3)
        out = m(_ids(rng, 128, (2, 12)))
        assert out.shape == [2, 3]


class TestResNet:
    def test_resnet18_train_batch(self):
        paddle.seed(0)
        m = paddle.vision.models.resnet18(num_classes=10)
        x = paddle.randn([2, 3, 32, 32])
        y = paddle.to_tensor(np.array([1, 2], np.int64))
        loss = paddle.nn.functional.cross_entropy(m(x), y)
        loss.backward()
        assert np.isfinite(float(loss))


class TestJitSaveLoad:
    def test_save_load_roundtrip(self, tmp_path):
        from paddle_tpu.static import InputSpec

        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        m.eval()
        x = paddle.randn([2, 8])
        ref = m(x).numpy()
        path = str(tmp_path / "model")
        paddle.jit.save(m, path, input_spec=[InputSpec([2, 8], "float32")])
        loaded = paddle.jit.load(path)
        out = loaded(x).numpy()
        np.testing.assert_allclose(ref, out, rtol=1e-5)


class TestMoE:
    def test_moe_forward_backward(self):
        from paddle_tpu.incubate.distributed.models.moe import MoELayer

        paddle.seed(3)
        d = 16
        experts = nn.LayerList([
            nn.Sequential(nn.Linear(d, 32), nn.GELU(), nn.Linear(32, d))
            for _ in range(4)])
        moe = MoELayer(d, experts, gate={"type": "gshard", "top_k": 2})
        x = paddle.randn([8, d])
        x.stop_gradient = False
        out = moe(x)
        assert out.shape == [8, d]
        (out.sum() + moe.l_aux).backward()
        assert x.grad is not None
        grads = [p.grad for p in experts.parameters()]
        assert any(g is not None for g in grads)


class TestHapi:
    def test_model_fit(self):
        from paddle_tpu.hapi import Model
        from paddle_tpu.io import TensorDataset

        paddle.seed(0)
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((64, 8)).astype(np.float32)
        ys = (xs.sum(1) > 0).astype(np.int64)
        ds = TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        model = Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(0.01,
                                            parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        hist = model.fit(ds, batch_size=16, epochs=2, verbose=0)
        assert len(hist) == 2
        logs = model.evaluate(ds, batch_size=16, verbose=0)
        assert logs["loss"] is not None
