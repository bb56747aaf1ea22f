"""ISSUE 15 tentpole: pipeline parallelism through the one-compilation
SPMD path — dp x mp x pp in a single replayable executable.

`distributed/pp_spmd.PipelineSpmdStep` stacks the uniform trunk over the
folded mesh's 'pp' axis and expresses the whole microbatch schedule
(lockstep GPipe ticks, jnp.roll stage shift -> GSPMD collective-permute,
value_and_grad backward) inside ONE lazy-captured op, so the steady-state
step replays through core/lazy.ReplayStep with zero dispatched ops and
zero per-step Python collectives — the same acceptance contract
tests/test_spmd.py pins for dp x mp (PR 6/8), now with pp >= 2.

Structure mirrors test_spmd.py: one dp2 x mp2 x pp2 gpt2-tiny leg is
shared module-wide and the tests run in file order (-p no:randomly in
tier-1): gate -> donation -> replay arming -> lint/describe -> parity
(disables the mesh for the oracle, so it must come last) -> refusals.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import lazy
from paddle_tpu.distributed import fleet, pp_spmd, spmd
from paddle_tpu.distributed.meta_parallel.pp_layers import \
    PipelineStageError
from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                               GPTPretrainingCriterion)
from paddle_tpu.profiler import explainer as _explain
from paddle_tpu.profiler import registry as _reg

V, T, B, M = 64, 16, 16, 2

N_WARM, N_STEADY = 8, 4


@pytest.fixture(scope="module", autouse=True)
def _spmd_module_boundary():
    yield
    spmd.disable()
    lazy.drop_plans("test module boundary")


def _init_fleet(dp=2, mp=2, pp=2, sharding=1, use_spmd=True):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "mp_degree": mp, "pp_degree": pp,
        "sharding_degree": sharding, "use_spmd": use_spmd}
    strategy.pipeline_configs = {"accumulate_steps": M}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _gpt2_tiny(n_layer=2):
    cfg = GPTConfig.preset("gpt2-tiny", vocab_size=V, n_layer=n_layer,
                           seq_len=T, dropout=0.0, n_head=2, d_model=32)
    paddle.seed(123)
    model = GPTForPretraining(GPTModel(cfg))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    return model, opt, GPTPretrainingCriterion()


def _batch():
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    return toks, np.roll(toks, -1, 1)


_LEG: dict = {}


def _shared_leg():
    """ONE dp2 x mp2 x pp2 leg: N_WARM warmup steps (record -> promote ->
    donate -> ReplayStep arm), then the N_STEADY gate window with every
    counter delta'd around it."""
    if _LEG:
        return _LEG
    _init_fleet()
    model, opt, crit = _gpt2_tiny()
    model = fleet.distributed_model(model)
    step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                    accumulate_steps=M)
    toks, labels = _batch()
    warm = [float(step.train_batch([toks, labels]))
            for _ in range(N_WARM)]
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    f0 = dict(_reg.counters("fastpath"))
    m0 = dict(_reg.counters("mp"))
    steady = [float(step.train_batch([toks, labels]))
              for _ in range(N_STEADY)]
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    f1 = dict(_reg.counters("fastpath"))
    deltas = {k: c1[k] - c0.get(k, 0) for k in c1}
    deltas.update({k: s1[k] - s0[k] for k in s1})
    deltas.update({f"fp_{k}": f1[k] - f0.get(k, 0) for k in f1})
    deltas["mp_bytes"] = sum(v - m0.get(k, 0)
                             for k, v in _reg.counters("mp").items()
                             if k.endswith(".bytes"))
    _LEG.update(step=step, model=model, opt=opt, losses=warm + steady,
                deltas=deltas, desc=spmd.describe_plans())
    return _LEG


class TestMeshFold:
    def test_pp_folds_to_three_axis_mesh(self):
        hcg = _init_fleet(dp=2, mp=2, pp=2)
        mesh = hcg.spmd_mesh()
        assert mesh is not None
        assert mesh.axis_names == ("dp", "pp", "mp")
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "dp": 2, "pp": 2, "mp": 2}
        assert spmd.enabled()
        # structured selection event, not a bare warning
        assert any(e.get("kind") == "spmd_pp_selected"
                   for e in _explain.events(kind="spmd_pp_selected"))

    def test_sharding_with_pp_folds_preserving_device_order(self):
        # ISSUE 16: pp>1 with sharding>1 FOLDS instead of refusing —
        # 'sharding' collapses into 'dp' via a device-array transpose,
        # so every device keeps its hcg (data, pipe, sharding, model)
        # coordinate and folded-'dp' collectives span exactly the union
        # of the hcg data and sharding groups
        _explain.clear()
        hcg = _init_fleet(dp=1, mp=2, pp=2, sharding=2)
        mesh = hcg.spmd_mesh()
        assert mesh is not None
        assert mesh.axis_names == ("dp", "pp", "mp")
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "dp": 2, "pp": 2, "mp": 2}
        assert spmd.enabled()
        for p in range(2):
            for s in range(2):
                for m in range(2):
                    assert mesh.devices[s, p, m] \
                        == hcg.mesh.devices[0, p, s, m]
        assert not _explain.events(kind="spmd_pp_refused")


class TestPpZero:
    """ISSUE 16 tentpole leg: pp=2 x sharding=2 (x mp=2) rides the SAME
    one-compilation path — ZeRO stays a layout fold into the folded
    'dp' axis, the microbatch schedule compiles once, and the steady
    state replays with zero dispatched ops and zero Python
    collectives, at dense-oracle loss parity."""

    def test_pp2_sharding2_zero_dispatch_and_dense_parity(self):
        from paddle_tpu.distributed.sharding import \
            group_sharded_parallel

        _init_fleet(dp=1, mp=2, pp=2, sharding=2)
        model, opt, crit = _gpt2_tiny()
        model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
        model = fleet.distributed_model(model)
        step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                        accumulate_steps=M)
        toks, labels = _batch()
        warm = [float(step.train_batch([toks, labels]))
                for _ in range(N_WARM)]
        c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
        f0 = dict(_reg.counters("fastpath"))
        steady = [float(step.train_batch([toks, labels]))
                  for _ in range(N_STEADY)]
        c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
        f1 = dict(_reg.counters("fastpath"))
        d = {k: c1[k] - c0.get(k, 0) for k in c1}
        d.update({k: s1[k] - s0[k] for k in s1})
        d.update({f"fp_{k}": f1[k] - f0.get(k, 0) for k in f1})
        losses = warm + steady
        assert np.isfinite(losses).all()
        assert d["captured_steps"] == N_STEADY
        assert d["nodes_built"] == 0
        assert d["step_compiles"] == 0
        assert d["python_collectives"] == 0
        assert _reg.counters("spmd")["python_collectives_per_step"] == 0
        assert d["fp_hits"] == N_STEADY and d["fp_misses"] == 0
        assert d["fp_replay_ops_dispatched"] == 0
        assert step.armed
        assert TestShardingLint._lint_mod().lint(
            spmd.describe_plans()) == []
        # the plan really shards over all three folded axes: stage
        # stacks over 'pp', ZeRO params over the folded 'dp', tensor
        # parallel over 'mp'
        plan = next(p for p in spmd.describe_plans()["plans"]
                    if p["first_op"] == "pp_pipeline_step")
        specs = [str(lf["spec"]) for lf in plan["leaves"]]
        assert any("'pp'" in s for s in specs)
        assert any("'dp'" in s for s in specs)
        assert any("'mp'" in s for s in specs)
        # dense oracle on the same seed/data (ZeRO + pipeline are pure
        # layout/schedule: the trajectory is the dense one)
        spmd.disable()
        model2, opt2, crit2 = _gpt2_tiny()
        toks_t, labels_t = paddle.to_tensor(toks), paddle.to_tensor(labels)

        def dense_step():
            with lazy.capture_guard(False), paddle.incubate.lazy_eval():
                loss = crit2(model2(toks_t), labels_t)
                loss.backward()
                opt2.step()
                opt2.clear_grad()
                return float(loss)

        dense = [dense_step() for _ in range(len(losses))]
        np.testing.assert_allclose(losses, dense, rtol=0, atol=1e-4)


class TestOneExecutable:
    """Acceptance gate: the steady dp x mp x pp step is ONE replayed
    executable — zero dispatched ops, zero Python collectives, zero new
    compiles; mp/pp bytes move through GSPMD only."""

    def test_steady_state_replays_zero_dispatch(self):
        leg = _shared_leg()
        d = leg["deltas"]
        assert np.isfinite(leg["losses"]).all()
        assert d["captured_steps"] == N_STEADY
        assert d["materializations"] == N_STEADY
        assert d["nodes_built"] == 0
        assert d["step_compiles"] == 0
        assert d["python_collectives"] == 0
        assert _reg.counters("spmd")["python_collectives_per_step"] == 0
        # per-collective byte counters report ZERO on the GSPMD path
        assert d["mp_bytes"] == 0
        # the replay fast path carried the whole window: every steady
        # step a hit, not one op dispatched
        assert d["fp_hits"] == N_STEADY
        assert d["fp_misses"] == 0
        assert d["fp_replay_ops_dispatched"] == 0
        assert leg["step"].armed

    def test_plan_is_stage_sharded(self):
        leg = _shared_leg()
        desc = leg["desc"]
        assert desc["mesh"]["axes"] == {"dp": 2, "pp": 2, "mp": 2}
        plans = [p for p in desc["plans"]
                 if p["first_op"] == "pp_pipeline_step"]
        assert len(plans) == 1
        leaves = plans[0]["leaves"]
        staged = [lf for lf in leaves
                  if lf.get("stage_membership") == "sharded"]
        replicated = [lf for lf in leaves
                      if lf.get("stage_membership") == "all"]
        assert staged, "no leaf is sharded over the 'pp' axis"
        assert replicated, "embeddings/head/scalars should stay on all " \
                           "stages"
        # the trunk stacks also keep their mp sharding inside the stage
        assert any("mp" in str(lf["spec"]) for lf in staged)


class TestDonation:
    def test_stage_params_donated(self):
        leg = _shared_leg()
        assert leg["deltas"]["donated_steps"] == N_STEADY, \
            "donation never engaged on the pp path"
        plan = next(p for p in leg["desc"]["plans"]
                    if p["first_op"] == "pp_pipeline_step")
        assert plan["donate_confirmed"]
        for lf in plan["leaves"]:
            if lf["carried"]:
                assert lf["donated"], lf
        # every stage-sharded carried class is donated (per-stage slices
        # update in place; the lint enforces the same contract)
        staged_carried = [lf for lf in plan["leaves"]
                          if lf.get("stage_membership") == "sharded"
                          and lf["carried"]]
        assert staged_carried
        stats = leg["step"].refresh_pipeline_stats()
        assert stats["donated"] == stats["carried"] > 0


class TestShardingLint:
    @staticmethod
    def _lint_mod():
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "sharding_lint.py")
        spec = importlib.util.spec_from_file_location("sharding_lint",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_live_pp_plan_is_clean(self):
        assert self._lint_mod().lint(_shared_leg()["desc"]) == []

    def test_flags_undonated_stage_param(self):
        slint = self._lint_mod()
        leaf = {"class": 0, "shape": [2, 32, 96], "dtype": "float32",
                "bytes": 2 * 32 * 96 * 4, "spec": ["pp", None, "mp"],
                "slot_flagged": True, "carried": True, "donated": False}
        desc = {"mesh": {"axes": {"dp": 2, "pp": 2, "mp": 2}},
                "plans": [{"spmd": True, "first_op": "pp_pipeline_step",
                           "donate_confirmed": True, "n_ops": 1,
                           "n_leaves": 1, "leaves": [leaf]}]}
        probs = slint.lint(desc)
        assert any("stage-sharded" in p for p in probs)
        assert slint.lint({**desc, "plans": [{
            **desc["plans"][0],
            "leaves": [dict(leaf, donated=True)]}]}) == []

    def test_flags_unsharded_pipeline_trunk(self):
        slint = self._lint_mod()
        leaf = {"class": 0, "shape": [2, 32, 96], "dtype": "float32",
                "bytes": 2 * 32 * 96 * 4, "spec": [None, None, "mp"],
                "slot_flagged": True, "carried": True, "donated": True}
        desc = {"mesh": {"axes": {"dp": 2, "pp": 2, "mp": 2}},
                "plans": [{"spmd": True, "first_op": "pp_pipeline_step",
                           "donate_confirmed": True, "n_ops": 1,
                           "n_leaves": 1, "leaves": [leaf]}]}
        assert any("no stage-sharded leaf" in p
                   for p in slint.lint(desc))


class TestMeshChange:
    def test_topology_change_drops_pp_plan(self):
        leg = _shared_leg()
        assert lazy.plans_alive() >= 1
        s0 = lazy.stats()
        _init_fleet(dp=4, mp=2, pp=1)  # back to the 2-axis mesh
        s1 = lazy.stats()
        assert s1["capture_invalidations"] > s0["capture_invalidations"]
        assert lazy.plans_alive() == 0
        # reinstall the pp mesh for the remaining consumers of the leg
        _init_fleet()


class TestParity:
    """Loss-trajectory parity, same tolerance contract as test_spmd.py.
    Runs after the gate tests: the oracles disable/churn the global
    mesh."""

    def test_pp2_matches_engine_1f1b_oracle(self):
        # engine oracle at pp=2 with degree-1 auto axes; same
        # seed/init/data
        _init_fleet(dp=1, mp=1, pp=2)
        model, opt, crit = _gpt2_tiny()
        model = fleet.distributed_model(model)
        step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                        accumulate_steps=M)
        toks, labels = _batch()
        ours = [float(step.train_batch([toks, labels]))
                for _ in range(4)]

        spmd.disable()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
            "sharding_degree": 1}
        strategy.pipeline_configs = {"accumulate_steps": M}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        model2, opt2, crit2 = _gpt2_tiny()
        engine = fleet.HybridParallelEngine(model2, opt2, hcg, strategy,
                                            criterion=crit2)
        oracle = [float(engine.train_batch([toks, labels]))
                  for _ in range(4)]
        # both paths are means over the same M microbatches; 1F1B vs
        # GPipe-autodiff only reorders fp32 reductions
        np.testing.assert_allclose(ours, oracle, rtol=2e-2, atol=1e-4)

    def test_dp_mp_pp_matches_dense(self):
        losses = _shared_leg()["losses"]
        spmd.disable()
        model, opt, crit = _gpt2_tiny()
        toks_np, labels_np = _batch()
        toks = paddle.to_tensor(toks_np)
        labels = paddle.to_tensor(labels_np)

        def dense_step():
            with lazy.capture_guard(False), paddle.incubate.lazy_eval():
                loss = crit(model(toks), labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

        dense = [dense_step() for _ in range(len(losses))]
        np.testing.assert_allclose(losses, dense, rtol=0, atol=1e-4)


class TestRefusals:
    def test_indivisible_stage_count_structured(self):
        _init_fleet(dp=1, mp=1, pp=2)
        model, opt, crit = _gpt2_tiny(n_layer=3)
        _explain.clear()
        with pytest.raises(PipelineStageError, match="not divisible"):
            pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                     accumulate_steps=M)
        evs = _explain.events(kind="spmd_pp_refused")
        assert evs and evs[-1]["reason"] == "stage_indivisible"

    def test_indivisible_batch_structured(self):
        _init_fleet(dp=1, mp=1, pp=2)
        model, opt, crit = _gpt2_tiny()
        step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                        accumulate_steps=M)
        rng = np.random.default_rng(1)
        toks = rng.integers(0, V, (B - 1, T)).astype(np.int64)
        with pytest.raises(PipelineStageError, match="not divisible"):
            step.train_batch([toks, np.roll(toks, -1, 1)])
        # the check runs on EVERY batch: a ragged batch after a good one
        # (an epoch's final partial batch) still refuses structurally
        good, glabels = _batch()
        assert np.isfinite(float(step.train_batch([good, glabels])))
        with pytest.raises(PipelineStageError, match="not divisible"):
            step.train_batch([toks, np.roll(toks, -1, 1)])

    def test_accepts_distributed_optimizer_wrapper(self):
        # a fleet.distributed_optimizer wrapper must not absorb the
        # parameter-list restructuring (the inner optimizer would keep
        # updating the stale per-layer params — silent plateau)
        _init_fleet(dp=1, mp=1, pp=2)
        model, opt, crit = _gpt2_tiny()
        wrapped = fleet.distributed_optimizer(opt)
        step = pp_spmd.PipelineSpmdStep(model, wrapped, criterion=crit,
                                        accumulate_steps=M)
        assert step.optimizer is opt
        assert opt._parameter_list == [
            p for p in step._grad_params if not p.stop_gradient]

    def test_step_requires_pp_mesh(self):
        _init_fleet(dp=4, mp=2, pp=1)
        model, opt, crit = _gpt2_tiny()
        with pytest.raises(RuntimeError, match="pp-folded"):
            pp_spmd.PipelineSpmdStep(model, opt, criterion=crit)


class TestExplicitMicrobatches:
    def test_accumulate_steps_below_pp_is_honored(self):
        # the lockstep schedule is correct for M < pp (bubblier, never
        # resized behind the user's back); M=1 also pins the unrolled
        # form (see _pipeline_loss for why M=1 unrolls)
        _init_fleet(dp=1, mp=1, pp=2)
        model, opt, crit = _gpt2_tiny()
        step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                        accumulate_steps=1)
        assert step.M == 1
        toks, labels = _batch()
        losses = [float(step.train_batch([toks, labels]))
                  for _ in range(2)]
        assert np.isfinite(losses).all() and losses[1] < losses[0]

    @pytest.mark.slow
    def test_scan_schedule_matches_unrolled(self):
        # the long-schedule lax.scan form must train the same trajectory
        # as the short-schedule unrolled form (same model/seed/data).
        # slow tier: two full warm legs (~7 s) of pure regression depth
        # — the unrolled form is already parity-pinned by the tier-1
        # gates above
        toks, labels = _batch()
        runs = {}
        for name, unroll in (("unrolled", 8), ("scan", 1)):
            _init_fleet(dp=1, mp=1, pp=2)
            model, opt, crit = _gpt2_tiny()
            step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                            accumulate_steps=M,
                                            unroll_ticks=unroll)
            runs[name] = [float(step.train_batch([toks, labels]))
                          for _ in range(3)]
        np.testing.assert_allclose(runs["scan"], runs["unrolled"],
                                   rtol=1e-4, atol=1e-6)


class TestHapiPath:
    def test_model_train_batch_selects_pp_step(self):
        from paddle_tpu import hapi

        _init_fleet(dp=2, mp=2, pp=2)
        model, opt, crit = _gpt2_tiny()
        model = fleet.distributed_model(model)
        m = hapi.Model(model)
        m.prepare(optimizer=opt, loss=crit)
        toks, labels = _batch()
        losses = [m.train_batch([toks], [labels])[0] for _ in range(4)]
        assert np.isfinite(losses).all()
        assert getattr(m, "_pp_step", None) is not None
        plans = spmd.describe_plans()["plans"]
        assert any(p["first_op"] == "pp_pipeline_step" for p in plans)
        # eval runs the plain network: it must see the TRAINED trunk
        # (sync_params_to_model), not the step-0 per-layer tensors
        _, res = m.eval_batch([toks], labels)
        assert res["loss"] is not None
        assert res["loss"] < losses[0], \
            "eval saw stale (untrained) per-layer weights"
        # multi-label batches refuse with guidance, not a TypeError
        with pytest.raises(ValueError, match="tokens, labels"):
            m.train_batch([toks], [labels, labels])

    @pytest.mark.slow
    def test_save_load_resumes_params_and_slots(self, tmp_path):
        # slow tier: two trained models (~11 s) of checkpoint-lifecycle
        # regression depth on top of the tier-1 hapi gate above.
        # fresh-process resume through the CANONICAL per-layer layout:
        # save() de-stacks params AND optimizer slots
        # (export_optimizer_state), so the checkpoint restores on every
        # path; the next pp step re-adopts the slots into stacks
        from paddle_tpu import hapi

        _init_fleet(dp=2, mp=2, pp=2)
        model, opt, crit = _gpt2_tiny()
        model = fleet.distributed_model(model)
        m = hapi.Model(model)
        m.prepare(optimizer=opt, loss=crit)
        toks, labels = _batch()
        for _ in range(3):
            m.train_batch([toks], [labels])
        prefix = str(tmp_path / "ck")
        m.save(prefix)
        # the .pdopt carries NO stacked keys — dense/engine restorable
        from paddle_tpu.framework import load as _fload

        opt_sd = _fload(prefix + ".pdopt")
        assert not any("pp_stack." in str(k) for k in opt_sd)
        assert opt_sd["_opt_step"] == 3
        ref = m.train_batch([toks], [labels])[0]  # step 4, original

        _init_fleet(dp=2, mp=2, pp=2)
        model2, opt2, crit2 = _gpt2_tiny()
        model2 = fleet.distributed_model(model2)
        m2 = hapi.Model(model2)
        m2.prepare(optimizer=opt2, loss=crit2)
        m2.load(prefix)
        # per-layer layout restores IMMEDIATELY (no deferral)
        assert opt2._opt_step == 3
        resumed = m2.train_batch([toks], [labels])[0]  # step 4, resumed
        # identical step 4 requires restored params AND Adam moments
        # AND the step count (bias correction)
        np.testing.assert_allclose(resumed, ref, rtol=1e-4, atol=1e-6)
        assert opt2._opt_step == opt._opt_step
