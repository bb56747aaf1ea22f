"""Replay-by-signature fast path (ISSUE 9): once a captured train step's
input signature is stable, lazy.ReplayStep replays the cached executable
with ZERO per-op Python — no dispatch, no node recording, no cursor walk —
demoting cursor verification to a periodic audit. These tests pin the
contract: bitwise parity with the plain capture path, zero dispatched ops
on replayed steps, audit-caught divergence (mutate_signature injection),
and audited first steps after drop_plans / donation toggles."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core import lazy
from paddle_tpu.core import dispatch
from paddle_tpu.profiler import registry
from paddle_tpu.testing import faults


def _make(seed=7):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    return net, opt


def _data(batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(batch, 16)).astype(np.float32),
            rng.normal(size=(batch, 4)).astype(np.float32))


def _body(net, opt, xt, yt):
    with paddle.incubate.lazy_eval():
        loss = ((net(xt) - yt) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss


def _params(net):
    return [np.asarray(lazy.force(p._data)) for p in net.parameters()]


def _fp():
    return dict(registry.counters("fastpath"))


class TestReplayStep:
    def test_arms_and_replays_bitwise(self):
        """Steady steps replay with zero dispatched ops; losses, params
        and the optimizer step count match the plain capture path
        bitwise (same executable, same inputs)."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=8)
        c0 = _fp()
        losses = [float(step()) for _ in range(30)]
        c1 = _fp()
        assert step.armed
        assert c1["arms"] - c0["arms"] >= 1
        assert c1["hits"] - c0["hits"] >= 15
        assert c1["ops_dispatched_per_step"] == 0
        assert c1["demotions"] - c0["demotions"] == 0

        net2, opt2 = _make()
        oracle = [float(_body(net2, opt2, xt, yt)) for _ in range(30)]
        assert losses == oracle
        for a, b in zip(_params(net), _params(net2)):
            assert (a == b).all()
        assert opt._opt_step == opt2._opt_step == 30

    def test_donation_survives_arming(self):
        """Arming must not freeze out buffer donation: the wrapper waits
        for the donate flag to stabilize before pinning an executable."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=50)
        s0 = lazy.stats()
        for _ in range(25):
            float(step())
        s1 = lazy.stats()
        assert step.armed
        assert s1["donated_steps"] - s0["donated_steps"] >= 10

    def test_fresh_batches_flow_through_args(self):
        """Arg-sourced leaves: new buffers with the same aval replay (the
        fingerprint checks avals, not identity); a shape change demotes
        with a structured cause and the step still computes correctly."""
        net, opt = _make()

        def body(xt, yt):
            return _body(net, opt, xt, yt)

        step = lazy.ReplayStep(body, optimizers=opt, audit_every=10)
        batches = [_data(seed=i) for i in range(25)]
        losses = [float(step(paddle.to_tensor(a), paddle.to_tensor(b)))
                  for a, b in batches]
        c = _fp()
        assert step.armed and c["hits"] >= 10

        net2, opt2 = _make()
        oracle = [float(_body(net2, opt2, paddle.to_tensor(a),
                              paddle.to_tensor(b))) for a, b in batches]
        assert losses == oracle

        # aval change: demote (cause arg_aval), fall back, still correct
        d0 = c.get("demote.arg_aval", 0)
        a, b = _data(batch=4, seed=99)
        l_small = float(step(paddle.to_tensor(a), paddle.to_tensor(b)))
        assert _fp().get("demote.arg_aval", 0) == d0 + 1
        l_oracle = float(_body(net2, opt2, paddle.to_tensor(a),
                               paddle.to_tensor(b)))
        assert l_small == l_oracle
        # the demoted step must advance the optimizer exactly ONCE (a
        # tick before the demote check would double-advance and skew
        # Adam bias correction for every later step)
        assert opt._opt_step == opt2._opt_step == 26
        a, b = batches[0]
        l_post = float(step(paddle.to_tensor(a), paddle.to_tensor(b)))
        l_post_oracle = float(_body(net2, opt2, paddle.to_tensor(a),
                                    paddle.to_tensor(b)))
        assert l_post == l_post_oracle

    def test_zero_dispatch_on_replayed_steps(self):
        """The acceptance telemetry: a replayed step dispatches ZERO ops
        through core.dispatch.forward."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=100)
        for _ in range(15):
            float(step())
        assert step.armed
        d0 = dispatch.ops_dispatched()
        for _ in range(5):
            float(step())
        assert dispatch.ops_dispatched() == d0
        assert _fp()["ops_dispatched_per_step"] == 0

    def test_replay_window_holds_with_tracing_and_a_checkpoint_interval(
            self, tmp_path):
        """The steady window's obligations, with span tracing armed and an
        async checkpoint handed to the writer thread every 5th step: every
        step of the window is a fast-path hit that dispatches no op, is
        captured and donated, and nothing demotes."""
        from paddle_tpu.incubate import checkpoint as ckpt
        from paddle_tpu.profiler import tracing

        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=100)
        mgr = ckpt.CheckpointManager(str(tmp_path), max_to_keep=2,
                                     async_save=True)
        was_on = tracing.enabled()
        tracing.enable()
        try:
            for _ in range(25):
                float(step())
            assert step.armed
            window = 20
            s0, c0, d0 = lazy.stats(), _fp(), dispatch.ops_dispatched()
            for i in range(1, window + 1):
                float(step())
                if i % 5 == 0:
                    mgr.save(ckpt.capture_training_state(net, opt), step=i)
            s1, c1, d1 = lazy.stats(), _fp(), dispatch.ops_dispatched()
            mgr.wait()
        finally:
            if not was_on:
                tracing.disable()
        assert c1["hits"] - c0["hits"] == window
        assert c1["misses"] == c0["misses"]
        assert c1["replay_ops_dispatched"] == c0["replay_ops_dispatched"]
        assert d1 == d0
        assert c1["demotions"] == c0["demotions"]
        assert s1["captured_steps"] - s0["captured_steps"] == window
        assert s1["donated_steps"] - s0["donated_steps"] == window
        assert ckpt.list_steps(str(tmp_path)) == [15, 20]

    def test_mutate_signature_caught_by_audit(self):
        """A perturbation the per-step fingerprint cannot see (a pinned
        leaf VALUE — identity and aval unchanged) is caught by the
        periodic audit's cross-check, demotes with a structured cause,
        re-promotes, and post-fallback steps match a state-synced oracle
        bitwise."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=5)
        for _ in range(12):
            float(step())
        assert step.armed
        c0 = _fp()
        faults.configure("mutate_signature:nth=2")
        try:
            for _ in range(12):
                float(step())
        finally:
            faults.reset()
        c1 = _fp()
        assert registry.counters("fault")["injected.mutate_signature"] >= 1
        assert c1["audit_runs"] > c0["audit_runs"]
        assert c1["demotions"] - c0["demotions"] >= 1
        assert c1.get("demote.audit_divergence", 0) \
            > c0.get("demote.audit_divergence", 0)
        # re-promotes after the fallback
        for _ in range(10):
            float(step())
        assert step.armed

        # post-fallback parity: sync an oracle to the (post-injection)
        # live state, then both must agree bitwise from here on
        net2, opt2 = _make()
        for p2, p in zip(net2.parameters(), net.parameters()):
            p2.set_value(paddle.to_tensor(np.asarray(lazy.force(p._data))))
        opt._ensure_accumulators()
        opt2._ensure_accumulators()
        opt2._opt_step = opt._opt_step
        for name, store in opt._accumulators.items():
            for t, t2 in zip(store.values(),
                             opt2._accumulators[name].values()):
                t2._data = lazy.force(t._data)
        post = [float(step()) for _ in range(8)]
        oracle = [float(_body(net2, opt2, xt, yt)) for _ in range(8)]
        assert post == oracle

    def test_inplace_restore_demotes_and_takes_effect(self):
        """set_value while armed (the in-place checkpoint-restore
        contract) must NOT be clobbered by the next replay's rebind: the
        external-mutation epoch demotes the fast path, the restored
        buffers are recorded, and the continuation matches an oracle
        restarted from the restored state bitwise."""
        from paddle_tpu.incubate.checkpoint import (
            capture_training_state, restore_training_state)
        import copy

        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=50)
        for _ in range(10):
            float(step())
        assert step.armed
        saved = copy.deepcopy({
            k: np.asarray(lazy.force(v._data)) if hasattr(v, "_data")
            else v
            for k, v in capture_training_state(net, opt)["model"].items()})
        saved_full = {"model": saved,
                      "optimizer": {k: (np.asarray(lazy.force(v._data))
                                        if hasattr(v, "_data") else v)
                                    for k, v in opt.state_dict().items()}}
        for _ in range(5):
            float(step())
        c0 = _fp()
        restore_training_state(net, opt, saved_full)
        post = [float(step()) for _ in range(6)]
        c1 = _fp()
        assert c1.get("demote.external_mutation", 0) \
            == c0.get("demote.external_mutation", 0) + 1

        # oracle: fresh loop restored from the same state
        net2, opt2 = _make()
        for _ in range(10):
            float(_body(net2, opt2, xt, yt))
        restore_training_state(net2, opt2, saved_full)
        oracle = [float(_body(net2, opt2, xt, yt)) for _ in range(6)]
        assert post == oracle

    def test_drop_plans_forces_audited_first_step(self):
        """drop_plans (checkpoint restore with changed avals, model
        surgery, mesh change) demotes the armed fast path: the first
        step after it runs the full recorded walk, then re-arms."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=50)
        for _ in range(15):
            float(step())
        assert step.armed
        c0 = _fp()
        lazy.drop_plans("test boundary")
        float(step())  # audited: full walk, no hit
        c1 = _fp()
        assert c1["hits"] == c0["hits"]
        assert c1.get("demote.plan_invalidated", 0) \
            == c0.get("demote.plan_invalidated", 0) + 1
        for _ in range(12):
            float(step())
        assert step.armed  # re-promoted and re-armed

    def test_capture_guard_off_demotes(self):
        """capture_guard(False) must bypass the armed replay too."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=50)
        for _ in range(15):
            float(step())
        assert step.armed
        c0 = _fp()
        with lazy.capture_guard(False):
            l_off = float(step())
        assert _fp()["hits"] == c0["hits"]  # no replay while disabled

    def test_periodic_audit_cadence(self):
        """Audits run every audit_every-th call and keep the fast path
        armed when nothing diverged."""
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = lazy.ReplayStep(lambda: _body(net, opt, xt, yt),
                               optimizers=opt, audit_every=4)
        for _ in range(10):
            float(step())
        assert step.armed
        c0 = _fp()
        for _ in range(16):
            float(step())
        c1 = _fp()
        assert c1["audit_runs"] - c0["audit_runs"] == 4
        assert c1["demotions"] == c0["demotions"]
        assert step.armed

    def test_incubate_entrypoint(self):
        x, y = _data()
        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        net, opt = _make()
        step = paddle.incubate.replay_step(
            lambda: _body(net, opt, xt, yt), optimizers=opt)
        for _ in range(12):
            float(step())
        assert step.armed
