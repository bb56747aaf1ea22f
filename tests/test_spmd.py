"""ISSUE 6 tentpole: one-compilation SPMD train step.

A captured whole-step plan (core/lazy.py) compiles ONCE under the global
('dp', 'mp') mesh with explicit NamedSharding in/out specs and
param/optimizer-slot donation; GSPMD inserts the dp gradient all-reduce
and mp collectives instead of Python (distributed/spmd.py). The manual
paths — eager per-op GSPMD and the HybridParallelEngine — stay as the
numeric oracles.

NOTE on structure: one gpt2-tiny dp x mp training leg (_shared_leg) is
expensive relative to the rest of tier-1, so the read-only consumers
share a single module-level leg and the tests run in file order
(-p no:randomly in the tier-1 line): gate → donation (+1 step) →
divergence (falls back, recovers) → lint → parity (disables the mesh
for the oracles, so it must come last)."""
import importlib.util
import os

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.core import lazy
from paddle_tpu.distributed import fleet, spmd
from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                               GPTPretrainingCriterion)
from paddle_tpu.profiler import registry as _reg

V, T, B, DP, MP = 64, 16, 16, 4, 2

N_WARM, N_STEADY = 8, 4


@pytest.fixture(scope="module", autouse=True)
def _spmd_module_boundary():
    yield
    # the mesh is process-global: never leak it into the next test file
    spmd.disable()
    lazy.drop_plans("test module boundary")


def _init_fleet(use_spmd, dp=DP, mp=MP, sharding=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "mp_degree": mp, "pp_degree": 1,
        "sharding_degree": sharding, "use_spmd": use_spmd}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _gpt2_tiny():
    # gpt2-tiny preset, shrunk for CPU; every mp-annotated dim divides
    # mp=2 (d_model 32, d_ff 128, vocab 64)
    cfg = GPTConfig.preset("gpt2-tiny", vocab_size=V, n_layer=2,
                           seq_len=T, dropout=0.0, n_head=2, d_model=32)
    paddle.seed(123)
    model = GPTForPretraining(GPTModel(cfg))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    return model, opt, GPTPretrainingCriterion()


def _batch():
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    return toks, np.roll(toks, -1, 1)


def _lazy_steps(model, opt, crit, toks, labels, n, capture=True):
    def step():
        with lazy.capture_guard(capture), paddle.incubate.lazy_eval():
            loss = crit(model(toks), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return float(loss)

    return [step() for _ in range(n)]


_LEG: dict = {}


def _shared_leg():
    """ONE gpt2-tiny dp x mp leg through the one-compilation path:
    N_WARM warmup steps (record → promote → donate), then an N_STEADY
    gate window with counters delta'd around it. Later tests keep
    training the same live model (file order is the contract)."""
    if _LEG:
        return _LEG
    _init_fleet(use_spmd=True)
    model, opt, crit = _gpt2_tiny()
    model = fleet.distributed_model(model)
    toks_np, labels_np = _batch()
    toks = spmd.shard_batch(paddle.to_tensor(toks_np))
    labels = spmd.shard_batch(paddle.to_tensor(labels_np))
    warm = _lazy_steps(model, opt, crit, toks, labels, N_WARM)
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    m0 = dict(_reg.counters("mp"))
    steady = _lazy_steps(model, opt, crit, toks, labels, N_STEADY)
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    deltas = {k: c1[k] - c0.get(k, 0) for k in c1}
    deltas.update({k: s1[k] - s0[k] for k in s1})
    deltas["mp_bytes"] = sum(v - m0.get(k, 0)
                             for k, v in _reg.counters("mp").items()
                             if k.endswith(".bytes"))
    _LEG.update(model=model, opt=opt, crit=crit, toks=toks,
                labels=labels, losses=warm + steady, deltas=deltas,
                desc=spmd.describe_plans())
    return _LEG


class TestSpecDerivation:
    """The shared mesh/axis-rules layer."""

    def test_per_arg_specs_broadcasts_one_spec(self):
        assert spmd.per_arg_specs(P("mp"), 3) == (P("mp"),) * 3
        assert spmd.per_arg_specs(None, 2) == (None, None)
        assert spmd.per_arg_specs((P("mp"), P()), 2) == (P("mp"), P())

    def test_param_pspec_rules(self):
        hcg = _init_fleet(use_spmd=False, dp=2, mp=2, sharding=2)
        mesh = hcg.spmd_mesh()
        assert mesh.axis_names == ("dp", "mp")
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "dp": 4, "mp": 2}
        # ColumnParallel / RowParallel annotations pass through
        assert spmd.param_pspec((None, "mp"), mesh) == P(None, "mp")
        assert spmd.param_pspec(("mp", None), mesh) == P("mp", None)
        # ZeRO 'sharding' folds onto 'dp' on the 2-axis mesh
        assert spmd.param_pspec(("sharding", None), mesh) == P("dp", None)
        # unannotated and unknown axes replicate
        assert spmd.param_pspec(None, mesh) == P()
        assert spmd.param_pspec(("pp", None), mesh) == P(None, None)
        # non-divisible dims fall back to replicated, divisible shard
        assert spmd.param_pspec((None, "mp"), mesh,
                                shape=(8, 7)) == P(None, None)
        assert spmd.param_pspec((None, "mp"), mesh,
                                shape=(8, 6)) == P(None, "mp")
        # on the engine's 4-axis mesh 'sharding' is real — no dp folding
        assert spmd.param_pspec(("sharding", None),
                                hcg.mesh) == P("sharding", None)

    def test_pp_topology_selects_spmd_mesh(self):
        # ISSUE 15: pp>1 is a first-class SPMD citizen — the folded mesh
        # gains a 'pp' axis (tests/test_spmd_pp.py drives the pipeline
        # step itself); ISSUE 16: pp>1 WITH sharding>1 folds too —
        # 'sharding' collapses into 'dp' exactly like the pp=1 case, and
        # no topology refuses the SPMD path anymore
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
            "sharding_degree": 1, "use_spmd": True}
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().spmd_mesh()
        assert mesh is not None and mesh.axis_names == ("dp", "pp", "mp")
        assert spmd.enabled()
        strategy.hybrid_configs["sharding_degree"] = 2
        strategy.hybrid_configs["dp_degree"] = 1
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().spmd_mesh()
        assert mesh is not None and mesh.axis_names == ("dp", "pp", "mp")
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "dp": 2, "pp": 2, "mp": 2}  # dp picks up the ZeRO fold
        assert spmd.enabled()


class TestOneCompilation:
    """Acceptance gate: the steady-state hybrid step is ONE compiled
    executable — no new compiles, no Python-dispatched collectives."""

    def test_steady_state_is_one_executable(self):
        leg = _shared_leg()
        deltas, desc = leg["deltas"], leg["desc"]
        assert np.isfinite(leg["losses"]).all()
        # one executable launch per step, zero re-recording
        assert deltas["captured_steps"] == N_STEADY
        assert deltas["materializations"] == N_STEADY
        assert deltas["nodes_built"] == 0
        # zero new step compiles in the window (the plain + donating
        # variants both compiled during warmup)
        assert deltas["step_compiles"] == 0
        # zero Python-dispatched collectives: GSPMD owns all comm
        assert deltas["python_collectives"] == 0
        assert _reg.counters("spmd")["python_collectives_per_step"] == 0
        # per-collective byte counters report ZERO on the GSPMD path
        assert deltas["mp_bytes"] == 0
        # exactly one plan, lowered under the mesh with real specs
        plans = [p for p in desc["plans"] if p["spmd"]]
        assert len(plans) == 1
        assert desc["mesh"]["axes"] == {"dp": DP, "mp": MP}
        sharded = [lf for lf in plans[0]["leaves"]
                   if lf["spec"] not in (None, "opaque")
                   and any(s for s in lf["spec"])]
        assert sharded, "no leaf carries a sharded PartitionSpec"
        assert any("mp" in str(lf["spec"]) for lf in sharded)


class TestDonation:
    """Optimizer slots are donated under the mesh, and _DONATED
    poisoning still trips on late reads of a donated payload."""

    def test_slots_donated_and_poisoned(self):
        leg = _shared_leg()
        assert leg["deltas"]["donated_steps"] == N_STEADY, \
            "donation never engaged on the SPMD path"
        plan = next(p for p in leg["desc"]["plans"] if p["spmd"])
        assert plan["donate_confirmed"]
        donated = [lf for lf in plan["leaves"] if lf["donated"]]
        assert donated, "no leaf donated"
        # every confirmed loop-carried optimizer buffer is donated
        # (this is also what tools/sharding_lint.py enforces)
        for lf in plan["leaves"]:
            if lf["carried"]:
                assert lf["donated"], lf
        # hold raw payload refs (NOT Tensors — those block donation via
        # the current-holder check) across one more donated step: the
        # poisoned slots must raise loudly, never return a dead buffer
        model, opt, crit = leg["model"], leg["opt"], leg["crit"]
        olds = [p._data for p in model.parameters()
                if isinstance(p._data, lazy.LazyArray)]
        assert olds
        s0 = lazy.stats()
        _lazy_steps(model, opt, crit, leg["toks"], leg["labels"], 1)
        assert lazy.stats()["donated_steps"] > s0["donated_steps"]
        tripped = 0
        for old in olds:
            try:
                np.asarray(old)
            except RuntimeError as e:
                assert "donated" in str(e)
                tripped += 1
        assert tripped, "no stale read tripped the _DONATED poison"
        # the live parameters read back fine
        for p in model.parameters():
            assert np.isfinite(np.asarray(lazy.force(p._data))).all()


class TestFallback:
    def test_divergence_falls_back_then_recovers(self):
        leg = _shared_leg()
        model, opt, crit = leg["model"], leg["opt"], leg["crit"]
        s0 = lazy.stats()
        # different batch shape: prefix-re-record fallback, not an error
        toks_np, labels_np = _batch()
        toks2 = spmd.shard_batch(paddle.to_tensor(toks_np[:8]))
        labels2 = spmd.shard_batch(paddle.to_tensor(labels_np[:8]))
        small = _lazy_steps(model, opt, crit, toks2, labels2, 2)
        s1 = lazy.stats()
        assert s1["capture_fallbacks"] > s0["capture_fallbacks"]
        assert np.isfinite(small).all()
        # the captured shape resumes replay
        _lazy_steps(model, opt, crit, leg["toks"], leg["labels"], 2)
        s2 = lazy.stats()
        assert s2["captured_steps"] > s1["captured_steps"]


class TestHapiPath:
    def test_model_train_batch_selects_spmd_step(self):
        # fleet.init(use_spmd) + hapi.Model: train_batch must ride the
        # lazy-SPMD step (auto dp-sharded batches, captured replay) —
        # regression: the step() closure was shadowed by an int local
        from paddle_tpu import hapi

        _init_fleet(use_spmd=True)
        model, opt, crit = _gpt2_tiny()
        model = fleet.distributed_model(model)
        m = hapi.Model(model)
        m.prepare(optimizer=opt, loss=crit)
        toks, labels = _batch()
        losses = [m.train_batch([toks], [labels])[0] for _ in range(6)]
        c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
        losses += [m.train_batch([toks], [labels])[0] for _ in range(2)]
        c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
        assert np.isfinite(losses).all()
        assert s1["captured_steps"] - s0["captured_steps"] == 2
        assert s1["nodes_built"] == s0["nodes_built"]
        assert c1["step_compiles"] == c0["step_compiles"]
        assert c1["python_collectives_per_step"] == 0
        assert any(p["spmd"] for p in spmd.describe_plans()["plans"])


class TestShardingLint:
    """tools/sharding_lint.py consumes describe_plans() JSON (stdlib
    only) and flags unsharded-but-shardable slots + missing donation."""

    @staticmethod
    def _lint_mod():
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "sharding_lint.py")
        spec = importlib.util.spec_from_file_location("sharding_lint",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _desc(self, leaf):
        return {"mesh": {"axes": {"dp": 4, "mp": 2}},
                "plans": [{"spmd": True, "first_op": "add",
                           "donate_confirmed": True, "n_ops": 1,
                           "n_leaves": 1, "leaves": [leaf]}]}

    def test_flags_replicated_shardable_slot(self):
        slint = self._lint_mod()
        leaf = {"class": 0, "shape": [1024, 256], "dtype": "float32",
                "bytes": 1024 * 256 * 4, "spec": [None, None],
                "slot_flagged": True, "carried": False, "donated": False}
        assert any("replicated" in p for p in slint.lint(self._desc(leaf)))
        # small buffers are below the lint floor
        leaf2 = dict(leaf, shape=[8, 8], bytes=256)
        assert slint.lint(self._desc(leaf2)) == []
        # sharded slot is clean
        leaf3 = dict(leaf, spec=[None, "mp"])
        assert slint.lint(self._desc(leaf3)) == []

    def test_flags_missing_donation(self):
        slint = self._lint_mod()
        leaf = {"class": 0, "shape": [64, 64], "dtype": "float32",
                "bytes": 64 * 64 * 4, "spec": [None, "mp"],
                "slot_flagged": True, "carried": True, "donated": False}
        assert any("not donated" in p for p in slint.lint(self._desc(leaf)))
        assert slint.lint(self._desc(dict(leaf, donated=True))) == []

    def test_live_plan_is_clean(self):
        assert self._lint_mod().lint(_shared_leg()["desc"]) == []


class TestParity:
    """gpt2-tiny dp x mp parity: the one-compilation step against the
    manual oracles (allclose fp32). Runs LAST: the oracles disable the
    global mesh, which drops the shared leg's captured plans."""

    def test_matches_manual_mp_engine_and_dense(self):
        losses = _shared_leg()["losses"]
        spmd.disable()  # oracles must not lower under the mesh
        # dense single-device oracle: identical seed/init/data, plain
        # eager record mode — full trajectory match
        model, opt, crit = _gpt2_tiny()
        toks_np, labels_np = _batch()
        toks, labels = paddle.to_tensor(toks_np), paddle.to_tensor(labels_np)
        dense = _lazy_steps(model, opt, crit, toks, labels, len(losses),
                            capture=False)
        np.testing.assert_allclose(losses, dense, rtol=0, atol=1e-4)
        # manual-mp oracle: HybridParallelEngine on the same dp x mp
        # topology — N per-op/engine-dispatched executables
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": DP, "mp_degree": MP, "pp_degree": 1,
            "sharding_degree": 1}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        model, opt, crit = _gpt2_tiny()
        engine = fleet.HybridParallelEngine(model, opt, hcg, strategy,
                                            criterion=crit)
        manual = [float(engine.train_batch([toks_np, labels_np]))
                  for _ in range(4)]
        # loss/grad are means over the engine's microbatches, so the
        # trajectories agree to numeric noise (fp32)
        np.testing.assert_allclose(losses[:4], manual, rtol=2e-2,
                                   atol=1e-4)


class TestMeshInstall:
    """Installing a mesh OVER None must drop plans captured pre-SPMD:
    their executables were compiled without in_shardings against
    single-device placements (runs last: it toggles the global mesh)."""

    def test_enable_over_none_drops_captured_plans(self):
        from paddle_tpu import nn, optimizer

        spmd.disable()
        paddle.seed(7)
        net = nn.Linear(8, 8)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        x = paddle.to_tensor(np.ones((4, 8), dtype=np.float32))

        def step():
            with paddle.incubate.lazy_eval():
                loss = (net(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

        losses = [step() for _ in range(6)]
        s0 = lazy.stats()
        assert s0["capture_promotions"] > 0
        hcg = _init_fleet(use_spmd=True)
        assert spmd.enabled()
        s1 = lazy.stats()
        assert s1["capture_invalidations"] > s0["capture_invalidations"], \
            "pre-SPMD plan survived the None -> mesh install"
        # the step re-records under the mesh and stays finite
        net = spmd.shard_model(net)
        losses += [step() for _ in range(2)]
        assert np.isfinite(losses).all()
