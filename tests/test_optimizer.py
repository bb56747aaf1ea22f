"""Optimizer/LR/clip tests (reference: unittests test_adam_op etc.)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _problem():
    paddle.seed(1)
    w = paddle.to_tensor(np.array([[2.0, -3.0]], np.float32),
                         stop_gradient=False)
    x = paddle.to_tensor(np.random.default_rng(0)
                         .standard_normal((64, 1)).astype(np.float32))
    target = x @ paddle.to_tensor(np.array([[1.0, 1.0]], np.float32))
    return w, x, target


def _train(opt_cls, steps=60, **kw):
    w, x, target = _problem()
    opt = opt_cls(parameters=[w], **kw)
    for _ in range(steps):
        loss = ((x @ w - target) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return float(((x @ w - target) ** 2).mean())


@pytest.mark.parametrize("opt_cls,kw", [
    (paddle.optimizer.SGD, {"learning_rate": 0.1}),
    (paddle.optimizer.Momentum, {"learning_rate": 0.05, "momentum": 0.9}),
    (paddle.optimizer.Adam, {"learning_rate": 0.1}),
    (paddle.optimizer.AdamW, {"learning_rate": 0.1, "weight_decay": 0.0}),
    (paddle.optimizer.Adagrad, {"learning_rate": 0.5}),
    (paddle.optimizer.RMSProp, {"learning_rate": 0.05, "steps": 200}),
    (paddle.optimizer.Adamax, {"learning_rate": 0.2, "steps": 200}),
    (paddle.optimizer.Lamb, {"learning_rate": 0.05, "lamb_weight_decay": 0.0}),
])
def test_optimizers_converge(opt_cls, kw):
    assert _train(opt_cls, **kw) < 0.05


def test_adam_matches_reference_formula():
    w = paddle.to_tensor(np.array([1.0], np.float32), stop_gradient=False)
    opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=[w])
    (w * 2).backward()  # grad = 2
    opt.step()
    # manual adam step 1
    m = 0.1 * 2
    v = 0.001 * 4
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    expect = 1.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(w.numpy(), [expect], rtol=1e-5)


def test_weight_decay_l2():
    w = paddle.to_tensor(np.array([1.0], np.float32), stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w],
                               weight_decay=0.5)
    (w * 0).sum().backward()
    opt.step()
    np.testing.assert_allclose(w.numpy(), [1.0 - 0.1 * 0.5], rtol=1e-6)


def test_grad_clip_global_norm():
    w1 = paddle.to_tensor(np.array([3.0], np.float32), stop_gradient=False)
    w2 = paddle.to_tensor(np.array([4.0], np.float32), stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1.0, parameters=[w1, w2],
                               grad_clip=nn.ClipGradByGlobalNorm(1.0))
    (w1 * 3 + w2 * 4).backward()  # grads 3, 4 → global norm 5
    opt.step()
    np.testing.assert_allclose(w1.numpy(), [3.0 - 3.0 / 5], rtol=1e-5)
    np.testing.assert_allclose(w2.numpy(), [4.0 - 4.0 / 5], rtol=1e-5)


def test_lr_schedulers():
    lr = paddle.optimizer.lr.StepDecay(0.1, step_size=2, gamma=0.5)
    vals = []
    for _ in range(5):
        vals.append(round(lr(), 5))
        lr.step()
    assert vals == [0.1, 0.1, 0.05, 0.05, 0.025]

    warm = paddle.optimizer.lr.LinearWarmup(0.1, warmup_steps=4, start_lr=0.0,
                                            end_lr=0.1)
    assert warm() < 0.1
    for _ in range(5):
        warm.step()
    assert warm() == pytest.approx(0.1)

    cos = paddle.optimizer.lr.CosineAnnealingDecay(0.1, T_max=10)
    assert cos() == pytest.approx(0.1)


def test_scheduler_with_optimizer():
    sched = paddle.optimizer.lr.StepDecay(0.1, step_size=1, gamma=0.1)
    w = paddle.to_tensor(np.array([1.0], np.float32), stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=sched, parameters=[w])
    w.sum().backward()
    opt.step()
    np.testing.assert_allclose(w.numpy(), [0.9], rtol=1e-6)
    sched.step()
    opt.clear_grad()
    w.sum().backward()
    opt.step()
    np.testing.assert_allclose(w.numpy(), [0.9 - 0.01], rtol=1e-5)


def test_optimizer_state_dict():
    w = paddle.to_tensor(np.array([1.0], np.float32), stop_gradient=False)
    w.name = "w"
    opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=[w])
    w.sum().backward()
    opt.step()
    sd = opt.state_dict()
    assert any("moment1" in k for k in sd)

    opt2 = paddle.optimizer.Adam(learning_rate=0.1, parameters=[w])
    w.sum().backward()
    opt2.step()  # create accumulators
    opt2.set_state_dict(sd)
    assert opt2._opt_step == 1


class TestLookAhead:
    """Reference incubate/optimizer/lookahead.py: k fast steps, then
    slow += alpha*(fast-slow) and fast resets to slow."""

    def test_matches_manual_slow_fast(self):
        from paddle_tpu.incubate.optimizer import LookAhead

        paddle.seed(3)
        p = paddle.to_tensor(np.array([10.0, -10.0], np.float32))
        p.stop_gradient = False
        inner = paddle.optimizer.SGD(learning_rate=1.0, parameters=[p])
        opt = LookAhead(inner, alpha=0.5, k=2)
        g = np.array([1.0, -1.0], np.float32)
        x0 = np.array([10.0, -10.0], np.float32)
        for step in range(4):
            p.grad = paddle.to_tensor(g)
            opt.step()
            opt.clear_grad()
        # manual: fast after 2 sgd steps = x0 - 2g; sync1: slow=x0+0.5*
        # ((x0-2g)-x0)=x0-g; fast=slow. two more steps -> fast=x0-3g;
        # sync2: slow=x0-g+0.5*((x0-3g)-(x0-g))=x0-2g
        np.testing.assert_allclose(np.asarray(p.numpy()), x0 - 2 * g,
                                   rtol=1e-6)

    def test_trains_mlp(self):
        from paddle_tpu.incubate.optimizer import LookAhead
        import paddle_tpu.nn as nn

        paddle.seed(5)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt = LookAhead(paddle.optimizer.Adam(
            learning_rate=0.05, parameters=net.parameters()), k=3)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(32, 8)).astype(np.float32)
        Y = (X @ rng.normal(size=(8, 1))).astype(np.float32)
        xt, yt = paddle.to_tensor(X), paddle.to_tensor(Y)
        losses = []
        for _ in range(30):
            loss = ((net(xt) - yt) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.2


class TestLBFGS:
    """Reference incubate/optimizer/lbfgs.py (torch-style closure API)."""

    def test_quadratic_exact(self):
        from paddle_tpu.incubate.optimizer import LBFGS

        p = paddle.to_tensor(np.array([3.0, -4.0], np.float32))
        p.stop_gradient = False
        target = np.array([1.0, 2.0], np.float32)
        opt = LBFGS(parameters=[p], learning_rate=1.0, max_iter=20,
                    line_search_fn="strong_wolfe")

        def closure():
            opt.clear_grad()
            loss = ((p - paddle.to_tensor(target)) ** 2).sum()
            loss.backward()
            return loss

        opt.step(closure)
        np.testing.assert_allclose(np.asarray(p.numpy()), target,
                                   rtol=1e-4, atol=1e-5)

    def test_rosenbrock_converges(self):
        from paddle_tpu.incubate.optimizer import LBFGS

        p = paddle.to_tensor(np.array([-1.2, 1.0], np.float32))
        p.stop_gradient = False
        opt = LBFGS(parameters=[p], learning_rate=1.0, max_iter=60,
                    history_size=10, line_search_fn="strong_wolfe")

        def closure():
            opt.clear_grad()
            a = p[1] - p[0] * p[0]
            b = 1.0 - p[0]
            loss = 100.0 * (a * a) + b * b
            loss.backward()
            return loss

        for _ in range(4):  # a few restarts of max_iter each
            opt.step(closure)
        np.testing.assert_allclose(np.asarray(p.numpy()), [1.0, 1.0],
                                   rtol=1e-2, atol=1e-2)

    def test_fixed_step_no_line_search(self):
        from paddle_tpu.incubate.optimizer import LBFGS

        p = paddle.to_tensor(np.array([5.0], np.float32))
        p.stop_gradient = False
        opt = LBFGS(parameters=[p], learning_rate=0.4, max_iter=30)

        def closure():
            opt.clear_grad()
            loss = (p * p).sum()
            loss.backward()
            return loss

        opt.step(closure)
        assert abs(float(p.numpy()[0])) < 1e-3

    def test_lookahead_state_roundtrip_mid_cycle(self):
        from paddle_tpu.incubate.optimizer import LookAhead

        def build():
            p = paddle.to_tensor(np.array([10.0, -10.0], np.float32))
            p.stop_gradient = False
            return p, LookAhead(paddle.optimizer.SGD(
                learning_rate=1.0, parameters=[p]), alpha=0.5, k=3)

        g = np.array([1.0, -1.0], np.float32)

        def run(opt, p, n):
            for _ in range(n):
                p.grad = paddle.to_tensor(g)
                opt.step()
                opt.clear_grad()

        # uninterrupted 5 steps
        p1, o1 = build()
        run(o1, p1, 5)
        # 2 steps, checkpoint, resume into a fresh instance, 3 more
        p2, o2 = build()
        run(o2, p2, 2)
        sd = o2.state_dict()
        p3 = paddle.to_tensor(np.asarray(p2.numpy()))
        p3.stop_gradient = False
        o3 = LookAhead(paddle.optimizer.SGD(learning_rate=1.0,
                                            parameters=[p3]),
                       alpha=0.5, k=3)
        o3.set_state_dict(sd)
        run(o3, p3, 3)
        np.testing.assert_allclose(np.asarray(p3.numpy()),
                                   np.asarray(p1.numpy()), rtol=1e-6)


def test_trainstep_follows_the_eager_trajectory():
    """jit.TrainStep must take the SAME optimizer steps as the eager loop
    it compiles: it once advanced the step counter twice per call (its
    own +1 on top of opt.step()'s), so Adam's bias correction ran at
    t = 2, 4, 6... and the loss fell along a different curve — found on
    the chip by comparing the smoke's TrainStep and lazy legs."""
    def build():
        paddle.seed(3)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        return net, opt

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(16, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.normal(size=(16, 4)).astype(np.float32))

    def body(net, opt):
        loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    net, opt = build()
    eager = [float(body(net, opt)) for _ in range(5)]
    net2, opt2 = build()
    train = paddle.jit.TrainStep(lambda: body(net2, opt2), net2, opt2)
    compiled = [float(train()) for _ in range(5)]
    np.testing.assert_allclose(compiled, eager, rtol=1e-5)
    assert int(opt2._opt_step) == opt._opt_step == 5
