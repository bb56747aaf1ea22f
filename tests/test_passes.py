"""Distributed program passes (reference distributed/passes/pass_base.py +
auto_parallel_{bf16,recompute,gradient_merge}.py semantics)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.passes import (PassContext, PassManager,
                                           new_pass, register_pass, PassBase)


def _build_mlp_program(lr=0.1, bsz=8, opt_cls=None):
    paddle.enable_static()
    main = paddle.static.Program()
    startup = paddle.static.Program()
    with paddle.static.program_guard(main, startup):
        x = paddle.static.data("x", [None, 16], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        h = paddle.static.nn.fc(x, 32, activation="relu")
        out = paddle.static.nn.fc(h, 1)
        loss = ((out - y) * (out - y)).mean()
        opt = (opt_cls or paddle.optimizer.SGD)(learning_rate=lr)
        opt.minimize(loss)
    return main, startup, loss


def _run_steps(main, startup, loss, n, seed=0, bsz=8):
    rng = np.random.default_rng(seed)
    exe = paddle.static.Executor()
    exe.run(startup)
    feeds = [{"x": rng.normal(size=(bsz, 16)).astype(np.float32),
              "y": rng.normal(size=(bsz, 1)).astype(np.float32)}
             for _ in range(n)]
    return [float(exe.run(main, feed=f, fetch_list=[loss])[0]) for f in feeds]


class TestPassFramework:
    def test_new_pass_unknown_raises(self):
        with pytest.raises(ValueError, match="not registered"):
            new_pass("definitely_not_a_pass")

    def test_register_and_apply_order(self):
        calls = []

        @register_pass("test_probe_pass")
        class Probe(PassBase):
            def _apply_single_impl(self, main, startup, context):
                calls.append(self.get_attr("tag"))

        try:
            pm = PassManager([new_pass("test_probe_pass", {"tag": "a"}),
                              new_pass("test_probe_pass", {"tag": "b"})])
            ctx = pm.apply([object()])
            assert calls == ["a", "b"]
            assert len(ctx.passes) == 2
        finally:
            PassBase._REGISTERED_PASSES.pop("test_probe_pass")

    def test_context_attrs(self):
        ctx = PassContext()
        ctx.set_attr("k", 3)
        assert ctx.get_attr("k") == 3
        assert ctx.get_attr("missing", "d") == "d"


class TestBF16Pass:
    def test_wraps_matmuls_and_still_trains(self):
        try:
            # six steps on six different random batches: whether the last
            # loss is below the first depended on the initial weights, and
            # so on which tests the worker had run before (ROADMAP D14)
            paddle.seed(7)
            main, startup, loss = _build_mlp_program()
            ctx = new_pass("auto_parallel_bf16").apply([main])
            assert ctx.get_attr("auto_parallel_bf16:wrapped_ops") >= 2
            losses = _run_steps(main, startup, loss, 6)
            assert all(np.isfinite(losses))
            assert losses[-1] < losses[0]
        finally:
            paddle.disable_static()

    def test_clone_isolated_from_pass(self):
        """Applying a pass to the train program must not leak casts into a
        clone(for_test=True) eval program: clones share the ops *list copy*,
        so passes replace records instead of mutating shared ones (advisor
        round-2 finding)."""
        try:
            main, startup, loss = _build_mlp_program()
            eval_prog = main.clone(for_test=True)
            before = list(eval_prog.ops)
            ctx = new_pass("auto_parallel_bf16").apply([main])
            assert ctx.get_attr("auto_parallel_bf16:wrapped_ops") >= 2
            # the eval clone still holds the original, unwrapped records
            assert all(a is b for a, b in zip(before, eval_prog.ops))
            assert not any(getattr(op, "_amp_wrapped", False)
                           for op in eval_prog.ops)
            # and the train program got fresh wrapped records
            assert sum(getattr(op, "_amp_wrapped", False)
                       for op in main.ops) >= 2
        finally:
            paddle.disable_static()

    def test_idempotent(self):
        try:
            main, _, _ = _build_mlp_program()
            new_pass("auto_parallel_bf16").apply([main])
            n1 = sum(getattr(op, "_amp_wrapped", False) for op in main.ops)
            new_pass("auto_parallel_bf16").apply([main])
            n2 = sum(getattr(op, "_amp_wrapped", False) for op in main.ops)
            assert n1 == n2  # double-apply must not double-wrap
        finally:
            paddle.disable_static()


class TestRecomputePass:
    def test_wraps_activations_same_numerics(self):
        try:
            paddle.seed(7)
            main, startup, loss = _build_mlp_program()
            base = _run_steps(main, startup, loss, 4, seed=1)

            paddle.seed(7)
            main2, startup2, loss2 = _build_mlp_program()
            ctx = new_pass("auto_parallel_recompute").apply([main2])
            assert ctx.get_attr("recompute:wrapped_ops") >= 1
            remat = _run_steps(main2, startup2, loss2, 4, seed=1)
            np.testing.assert_allclose(base, remat, rtol=1e-5)
        finally:
            paddle.disable_static()


class TestGradientMergePass:
    def test_k_step_accumulation_matches_big_batch(self):
        """k merged micro-steps with avg == one step on the concatenated
        batch (SGD linearity) — reference gradient-merge equivalence."""
        try:
            rng = np.random.default_rng(5)
            xs = rng.normal(size=(16, 16)).astype(np.float32)
            ys = rng.normal(size=(16, 1)).astype(np.float32)

            paddle.seed(11)
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_gradient_merge",
                     {"k_steps": 2, "avg": True}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            exe.run(main, feed={"x": xs[:8], "y": ys[:8]},
                    fetch_list=[loss])
            exe.run(main, feed={"x": xs[8:], "y": ys[8:]},
                    fetch_list=[loss])  # k=2: update applies here
            scope = paddle.static.global_scope()
            merged_params = [np.asarray(scope.vars[pv.name]).copy()
                             for pv, _ in main.params]
            assert merged_params

            paddle.seed(11)
            scope.vars.clear()
            main2, startup2, loss2 = _build_mlp_program()
            exe2 = paddle.static.Executor()
            exe2.run(startup2)
            exe2.run(main2, feed={"x": xs, "y": ys}, fetch_list=[loss2])
            big_params = [np.asarray(scope.vars[pv.name])
                          for pv, _ in main2.params]

            for i, (a, b) in enumerate(zip(merged_params, big_params)):
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6,
                                           err_msg=f"param #{i} diverged")
        finally:
            paddle.disable_static()

    def test_no_update_until_k(self):
        try:
            paddle.seed(3)
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_gradient_merge",
                     {"k_steps": 3}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            scope = paddle.static.global_scope()
            rng = np.random.default_rng(6)
            feed = {"x": rng.normal(size=(8, 16)).astype(np.float32),
                    "y": rng.normal(size=(8, 1)).astype(np.float32)}
            exe.run(main, feed=feed, fetch_list=[loss])  # run 1: accumulate
            before = {k: np.asarray(v).copy() for k, v in scope.vars.items()
                      if not k.startswith("@")}
            assert before, "params must exist in the scope after run 1"
            exe.run(main, feed=feed, fetch_list=[loss])  # run 2: accumulate
            after2 = {k: np.asarray(v) for k, v in scope.vars.items()
                      if not k.startswith("@")}
            for k in before:  # runs 1,2: params frozen
                np.testing.assert_array_equal(before[k], after2[k])
            exe.run(main, feed=feed, fetch_list=[loss])  # run 3: apply
            after3 = {k: np.asarray(v) for k, v in scope.vars.items()
                      if not k.startswith("@")}
            assert any(not np.array_equal(before[k], after3[k])
                       for k in before)  # run 3 applies
        finally:
            paddle.disable_static()


class TestFuseAllReducePass:
    def test_documented_noop(self):
        ctx = new_pass("fuse_all_reduce").apply([object()])
        assert "combiner" in ctx.get_attr("fuse_all_reduce:note")


class TestAmpO2Pass:
    def test_bf16_o2_master_weights_and_numerics(self):
        try:
            paddle.seed(21)
            main, startup, loss = _build_mlp_program()
            base = _run_steps(main, startup, loss, 5, seed=2)

            paddle.seed(21)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build_mlp_program()
            ctx = new_pass("auto_parallel_amp",
                           {"level": "O2", "dtype": "bfloat16"}).apply(
                [main2])
            assert ctx.get_attr("auto_parallel_amp:o2") == "bfloat16"
            o2 = _run_steps(main2, startup2, loss2, 5, seed=2)
            assert np.isfinite(o2).all()
            np.testing.assert_allclose(base, o2, rtol=5e-2, atol=5e-2)
            # masters stay fp32 in the scope
            scope = paddle.static.global_scope()
            for pv, _ in main2.params:
                assert np.asarray(scope.vars[pv.name]).dtype == np.float32
        finally:
            paddle.disable_static()

    def test_fp16_overflow_skips_update_and_decreases_scale(self):
        try:
            paddle.seed(5)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_amp",
                     {"level": "O2", "dtype": "float16",
                      "init_loss_scaling": 1.0e30}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            scope = paddle.static.global_scope()
            rng = np.random.default_rng(1)
            feed = {"x": rng.normal(size=(8, 16)).astype(np.float32),
                    "y": rng.normal(size=(8, 1)).astype(np.float32)}
            exe.run(main, feed=feed, fetch_list=[loss])
            before = {pv.name: np.asarray(scope.vars[pv.name]).copy()
                      for pv, _ in main.params}
            exe.run(main, feed=feed, fetch_list=[loss])
            for pv, _ in main.params:  # overflow -> update skipped
                np.testing.assert_array_equal(before[pv.name],
                                              scope.vars[pv.name])
            assert float(scope.vars["@amp@scale"]) < 1.0e30  # decreased
        finally:
            paddle.disable_static()


class TestShardingPass:
    def test_matches_unsharded_and_shards_opt_state(self):
        try:
            paddle.seed(31)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program(
                opt_cls=paddle.optimizer.Adam)
            base = _run_steps(main, startup, loss, 4, seed=3)

            paddle.seed(31)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build_mlp_program(
                opt_cls=paddle.optimizer.Adam)
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 4}).apply([main2])
            shd = _run_steps(main2, startup2, loss2, 4, seed=3)
            np.testing.assert_allclose(base, shd, rtol=1e-4, atol=1e-5)
            scope = paddle.static.global_scope()
            moments = [n for n in scope.vars if "@moment" in n]
            assert moments
            sharded = [n for n in moments
                       if len(scope.vars[n].sharding.device_set) == 4]
            assert sharded, f"no ZeRO-sharded state among {moments}"
        finally:
            paddle.disable_static()


class TestStrategyComposition:
    def test_amp_plus_sharding_from_strategy_flags(self):
        from paddle_tpu.distributed.passes import apply_pass_by_strategy
        from paddle_tpu.distributed import fleet

        try:
            paddle.seed(41)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program(
                opt_cls=paddle.optimizer.Adam)
            base = _run_steps(main, startup, loss, 4, seed=4)

            paddle.seed(41)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build_mlp_program(
                opt_cls=paddle.optimizer.Adam)
            strategy = fleet.DistributedStrategy()
            strategy.amp = True
            strategy.amp_configs = {"level": "O2"}  # bf16 O2
            strategy.sharding = True
            strategy.sharding_configs = {"sharding_degree": 2}
            apply_pass_by_strategy(main2, strategy)
            assert getattr(main2, "amp_o2_dtype", None) == "bfloat16"
            assert getattr(main2, "sharding_degree", 1) == 2
            combo = _run_steps(main2, startup2, loss2, 4, seed=4)
            np.testing.assert_allclose(base, combo, rtol=5e-2, atol=5e-2)
        finally:
            paddle.disable_static()


class TestGradClipPass:
    def test_clip_bounds_update_magnitude(self):
        try:
            paddle.seed(9)
            paddle.static.global_scope().vars.clear()
            # huge targets -> huge grads; clip_norm must bound the step
            main, startup, loss = _build_mlp_program(lr=1.0)
            ctx = new_pass("auto_parallel_grad_clip",
                           {"clip_norm": 0.1}).apply([main])
            assert ctx.get_attr("grad_clip:optimizers") == 1
            exe = paddle.static.Executor()
            exe.run(startup)
            scope = paddle.static.global_scope()
            rng = np.random.default_rng(2)
            feed = {"x": rng.normal(size=(8, 16)).astype(np.float32),
                    "y": (rng.normal(size=(8, 1)) * 1e4).astype(np.float32)}
            before = {pv.name: np.asarray(init).copy()
                      for pv, init in main.params}
            exe.run(main, feed=feed, fetch_list=[loss])
            total_sq = 0.0
            for pv, _ in main.params:
                delta = np.asarray(scope.vars[pv.name]) - before[pv.name]
                total_sq += float((delta ** 2).sum())
            # lr=1.0, global grad norm clipped to 0.1 -> update norm <= 0.1
            assert np.sqrt(total_sq) <= 0.1 + 1e-5
        finally:
            paddle.disable_static()

    def test_no_optimizer_raises(self):
        try:
            paddle.enable_static()
            prog = paddle.static.Program()
            with pytest.raises(ValueError, match="no recorded optimizer"):
                new_pass("auto_parallel_grad_clip").apply([prog])
        finally:
            paddle.disable_static()


class TestOptimizerSwapPasses:
    """auto_parallel_lars / auto_parallel_lamb (reference
    fleet/meta_optimizers/{lars,lamb}_optimizer.py inner-optimizer swap)."""

    def _parity(self, pass_name, inner_cls, direct_cls):
        paddle.seed(51)
        paddle.static.global_scope().vars.clear()
        main, startup, loss = _build_mlp_program(opt_cls=inner_cls)
        ctx = new_pass(pass_name).apply([main])
        assert ctx.get_attr(f"{pass_name}:swapped") == 1
        swapped = _run_steps(main, startup, loss, 4, seed=5)

        paddle.seed(51)
        paddle.static.global_scope().vars.clear()
        main2, startup2, loss2 = _build_mlp_program(opt_cls=direct_cls)
        direct = _run_steps(main2, startup2, loss2, 4, seed=5)
        np.testing.assert_allclose(swapped, direct, rtol=1e-5, atol=1e-6)
        # the swapped update rule is actually live: params moved
        scope = paddle.static.global_scope()
        moved = [pv.name for pv, init in main2.params
                 if not np.allclose(np.asarray(scope.vars[pv.name]),
                                    np.asarray(init))]
        assert moved

    def test_lars_pass_matches_direct_lars(self):
        try:
            self._parity("auto_parallel_lars", paddle.optimizer.Momentum,
                         paddle.optimizer.Lars)
        finally:
            paddle.disable_static()

    def test_lamb_pass_matches_direct_lamb(self):
        try:
            # the pass copies the inner Adam's epsilon (1e-8), like the
            # reference lamb_optimizer; match it in the direct build
            self._parity(
                "auto_parallel_lamb", paddle.optimizer.Adam,
                lambda learning_rate: paddle.optimizer.Lamb(
                    learning_rate=learning_rate, epsilon=1e-8))
        finally:
            paddle.disable_static()

    def test_lars_rejects_adam_inner(self):
        try:
            paddle.static.global_scope().vars.clear()
            main, _, _ = _build_mlp_program(opt_cls=paddle.optimizer.Adam)
            with pytest.raises(ValueError, match="Momentum inner"):
                new_pass("auto_parallel_lars").apply([main])
        finally:
            paddle.disable_static()

    def test_lamb_rejects_adamw_and_weight_decay(self):
        try:
            paddle.static.global_scope().vars.clear()
            main, _, _ = _build_mlp_program(opt_cls=paddle.optimizer.AdamW)
            with pytest.raises(ValueError, match="Adam inner"):
                new_pass("auto_parallel_lamb").apply([main])
            paddle.static.global_scope().vars.clear()
            main2, _, _ = _build_mlp_program(
                opt_cls=lambda learning_rate: paddle.optimizer.Adam(
                    learning_rate=learning_rate, weight_decay=1e-4))
            with pytest.raises(ValueError, match="weight_decay"):
                new_pass("auto_parallel_lamb").apply([main2])
        finally:
            paddle.disable_static()

    def test_strategy_flags_compose(self):
        from paddle_tpu.distributed.passes import apply_pass_by_strategy
        from paddle_tpu.distributed import fleet

        try:
            paddle.static.global_scope().vars.clear()
            main, _, _ = _build_mlp_program(opt_cls=paddle.optimizer.Adam)
            strategy = fleet.DistributedStrategy()
            strategy.lamb = True
            ctx = apply_pass_by_strategy(main, strategy)
            assert ctx.get_attr("auto_parallel_lamb:swapped") == 1
            from paddle_tpu.optimizer import Lamb

            assert isinstance(main.minimize_reqs[0][0], Lamb)
        finally:
            paddle.disable_static()


class TestLocalSGDPass:
    """auto_parallel_localsgd (reference
    fleet/meta_optimizers/localsgd_optimizer.py): k local steps per
    replica, periodic parameter averaging."""

    def test_duplicated_shards_match_smaller_batch_run(self):
        # both replicas see identical rows -> local steps identical ->
        # the periodic average is a no-op and the run must equal a
        # single-replica run on one shard's data
        try:
            rng = np.random.default_rng(7)
            xs = [rng.normal(size=(4, 16)).astype(np.float32)
                  for _ in range(5)]
            ys = [rng.normal(size=(4, 1)).astype(np.float32)
                  for _ in range(5)]

            paddle.seed(61)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_localsgd",
                     {"k_steps": 2}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            dup = [float(exe.run(main,
                                 feed={"x": np.concatenate([x, x]),
                                       "y": np.concatenate([y, y])},
                                 fetch_list=[loss])[0])
                   for x, y in zip(xs, ys)]

            paddle.seed(61)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build_mlp_program()
            exe2 = paddle.static.Executor()
            exe2.run(startup2)
            solo = [float(exe2.run(main2, feed={"x": x, "y": y},
                                   fetch_list=[loss2])[0])
                    for x, y in zip(xs, ys)]
            np.testing.assert_allclose(dup, solo, rtol=1e-4, atol=1e-5)
        finally:
            paddle.disable_static()

    def test_periodic_param_sync(self):
        # different shards -> replicas diverge between syncs and are
        # identical right after every k-th run (begin_step=1: run 1 syncs)
        try:
            paddle.seed(62)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_localsgd",
                     {"k_steps": 3, "begin_step": 1}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            scope = paddle.static.global_scope()
            rng = np.random.default_rng(8)
            pnames = [pv.name for pv, _ in main.params
                      if not pv.stop_gradient]

            def replicas_equal():
                # divergent per-replica copies live under @lsgd@rep@;
                # canonical names always hold the untiled mean snapshot
                for n in pnames:
                    assert tuple(np.asarray(scope.vars[n]).shape) == tuple(
                        np.asarray(scope.vars["@lsgd@rep@" + n]).shape[1:])
                return all(
                    np.allclose(np.asarray(scope.vars["@lsgd@rep@" + n])[0],
                                np.asarray(scope.vars["@lsgd@rep@" + n])[1])
                    for n in pnames)

            for run in range(1, 7):
                exe.run(main,
                        feed={"x": rng.normal(size=(8, 16)).astype(
                            np.float32),
                            "y": rng.normal(size=(8, 1)).astype(
                                np.float32)},
                        fetch_list=[loss])
                if run == 1 or run % 3 == 0:
                    assert replicas_equal(), f"run {run}: expected sync"
                else:
                    assert not replicas_equal(), \
                        f"run {run}: expected divergence"
        finally:
            paddle.disable_static()


class TestFP16AllreducePass:
    """auto_parallel_fp16_allreduce (reference
    fleet/meta_optimizers/fp16_allreduce_optimizer.py): the dp grad
    reduce runs in half precision."""

    def test_matches_plain_run_within_half_precision(self):
        try:
            rng = np.random.default_rng(9)
            feeds = [{"x": rng.normal(size=(8, 16)).astype(np.float32),
                      "y": rng.normal(size=(8, 1)).astype(np.float32)}
                     for _ in range(4)]

            paddle.seed(71)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            exe = paddle.static.Executor()
            exe.run(startup)
            base = [float(exe.run(main, feed=f, fetch_list=[loss])[0])
                    for f in feeds]

            paddle.seed(71)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main2])
            ctx = new_pass("auto_parallel_fp16_allreduce").apply([main2])
            assert ctx.get_attr("fp16_allreduce:dtype") == "float16"
            exe2 = paddle.static.Executor()
            exe2.run(startup2)
            half = [float(exe2.run(main2, feed=f, fetch_list=[loss2])[0])
                    for f in feeds]
            np.testing.assert_allclose(base, half, rtol=5e-2, atol=5e-3)
        finally:
            paddle.disable_static()


class TestReplicaModeGuards:
    def test_localsgd_plus_fp16_allreduce_raises(self):
        try:
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_localsgd", {"k_steps": 2}).apply([main])
            new_pass("auto_parallel_fp16_allreduce").apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            with pytest.raises(ValueError, match="purely local"):
                exe.run(main, feed={"x": np.zeros((8, 16), np.float32),
                                    "y": np.zeros((8, 1), np.float32)},
                        fetch_list=[loss])
        finally:
            paddle.disable_static()

    def test_indivisible_batch_raises(self):
        try:
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_fp16_allreduce").apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            with pytest.raises(ValueError, match="divisible"):
                exe.run(main, feed={"x": np.zeros((7, 16), np.float32),
                                    "y": np.zeros((7, 1), np.float32)},
                        fetch_list=[loss])
        finally:
            paddle.disable_static()


class TestLocalSGDCheckpoint:
    def test_save_collapses_replica_axis_and_load_resumes(self, tmp_path):
        import pickle

        try:
            paddle.seed(63)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_localsgd",
                     {"k_steps": 3, "begin_step": 0}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            rng = np.random.default_rng(10)

            def step():
                return float(exe.run(
                    main,
                    feed={"x": rng.normal(size=(8, 16)).astype(np.float32),
                          "y": rng.normal(size=(8, 1)).astype(np.float32)},
                    fetch_list=[loss])[0])

            step(); step()  # mid-interval: replicas have diverged
            prefix = str(tmp_path / "ck")
            paddle.static.save(main, prefix)
            with open(prefix + ".pdparams", "rb") as f:
                saved = pickle.load(f)
            scope = paddle.static.global_scope()
            for pv, _ in main.params:
                canon = np.asarray(scope.vars[pv.name])
                rep = np.asarray(scope.vars["@lsgd@rep@" + pv.name])
                # canonical scope entry is untiled; replica copies are
                # divergent and live only under the reserved name
                assert rep.shape == (2,) + canon.shape
                assert not np.allclose(rep[0], rep[1])
                np.testing.assert_allclose(canon, rep.mean(axis=0),
                                           rtol=1e-4, atol=1e-6)
                # the checkpoint records exactly the canonical snapshot
                assert saved[pv.name].shape == canon.shape
                np.testing.assert_allclose(saved[pv.name], canon, rtol=1e-6)
            opt_saved = pickle.load(open(prefix + ".pdopt", "rb"))
            assert not any(n.startswith("@lsgd@") for n in opt_saved)
            # load back into the live scope and keep training: replica
            # copies are dropped, training resumes from the synced state
            paddle.static.load(main, prefix)
            assert "@lsgd@rep@" + main.params[0][0].name not in scope.vars
            assert np.isfinite(step())
        finally:
            paddle.disable_static()

    def test_startup_reinit_after_localsgd_runs_clean(self):
        # re-running the startup program mid-training must drop replica
        # copies/counters and keep working (review r4: this crashed with
        # KeyError '@lsgd@cyc' when state outlived a reinit)
        try:
            paddle.seed(64)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build_mlp_program()
            new_pass("auto_parallel_sharding",
                     {"sharding_degree": 2}).apply([main])
            new_pass("auto_parallel_localsgd",
                     {"k_steps": 2}).apply([main])
            exe = paddle.static.Executor()
            exe.run(startup)
            feed = {"x": np.random.default_rng(0).normal(
                size=(8, 16)).astype(np.float32),
                "y": np.zeros((8, 1), np.float32)}
            exe.run(main, feed=feed, fetch_list=[loss])
            # reinit mid-training (the default startup program routes
            # through the Executor's real startup branch)
            exe.run(paddle.static.default_startup_program())
            scope = paddle.static.global_scope()
            assert not any(n.startswith("@lsgd@") for n in scope.vars)
            r = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(float(r[0]))
        finally:
            paddle.disable_static()
