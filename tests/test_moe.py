"""ISSUE 20 tentpole: MoE with expert parallelism on the one-compile path.

Fixed-shape top-k routing (nn/moe/gate.py) makes data-dependent routing
shape-INVARIANT, so a GPT-with-MoE train step captures once and replays
with zero post-warmup compiles; expert banks shard over the 'ep' mesh
axis and GSPMD lowers the dispatch/combine resharding as the expert
all-to-all (nn/moe/layer.py, distributed/spmd.py).

NOTE on structure: like test_spmd.py, one gpt2-tiny-moe dp=2 x ep=2 leg
(_moe_leg) is shared by the read-only consumers and the tests run in
file order (-p no:randomly in the tier-1 line): eager/degenerate/parity
tests first (no mesh — MoEMLP construction must not see an 'ep' axis),
then the SPMD leg gate, lint, and LAST the ep=1 parity leg (it
re-installs the mesh, dropping the shared leg's plans).
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import lazy
from paddle_tpu.distributed import fleet, spmd
from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                               GPTPretrainingCriterion)
from paddle_tpu.nn.moe import (MoEConfigError, MoEMLP, TopKGate,
                               metrics as moe_metrics, moe_capacity,
                               validate_moe_config)
from paddle_tpu.ops import activation as F_act
from paddle_tpu.profiler import explainer as _explain
from paddle_tpu.profiler import registry as _reg

V, T, B = 64, 16, 8
N_WARM, N_STEADY = 8, 20


@pytest.fixture(scope="module", autouse=True)
def _moe_module_boundary():
    yield
    spmd.disable()
    lazy.drop_plans("test module boundary")


def _tools_mod(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestValidation:
    """Satellite: structured up-front hyperparameter refusal — a bad MoE
    config fails at construction with a named reason + explainer event,
    never as an opaque shape error inside a trace."""

    def test_each_refusal_reason(self):
        cases = [
            (dict(num_experts=0, top_k=1, capacity_factor=1.0),
             "no_experts"),
            (dict(num_experts=4, top_k=5, capacity_factor=1.0),
             "top_k_exceeds_experts"),
            (dict(num_experts=4, top_k=2, capacity_factor=0.5),
             "capacity_factor_too_small"),
            (dict(num_experts=4, top_k=2, capacity_factor=1.0, ep=3),
             "experts_indivisible_by_ep"),
        ]
        for kwargs, reason in cases:
            with pytest.raises(MoEConfigError) as ei:
                validate_moe_config(**kwargs)
            assert reason in str(ei.value)
            evs = _explain.events(kind="moe_config_refused")
            assert evs and evs[-1]["reason"] == reason
            assert evs[-1]["num_experts"] == kwargs["num_experts"]

    def test_valid_configs_pass(self):
        validate_moe_config(4, 2, 1.25)
        validate_moe_config(8, 1, 1.0, ep=4)

    def test_gpt_config_validates(self):
        with pytest.raises(MoEConfigError):
            GPTConfig.preset("gpt2-tiny-moe", moe_top_k=8)
        # and pp>1 on an MoE trunk is refused with a named reason
        from paddle_tpu.distributed.meta_parallel.pp_layers import \
            PipelineStageError

        cfg = GPTConfig.preset("gpt2-tiny-moe", vocab_size=V,
                               seq_len=T, n_head=2, d_model=32)
        model = GPTForPretraining(GPTModel(cfg))
        with pytest.raises(PipelineStageError):
            model.pipeline_parts(2)
        evs = _explain.events(kind="spmd_pp_refused")
        assert evs and evs[-1]["reason"] == "moe_trunk"

    def test_capacity_formula(self):
        assert moe_capacity(16, 4, 2, 1.25) == 10  # ceil(16*1.25*2/4)
        assert moe_capacity(16, 4, 1, 1.0) == 4
        assert moe_capacity(1, 64, 1, 1.0) == 1    # floored at 1


class TestDegenerateRouting:
    """Satellite: the routing edge cases — total collapse onto one
    expert (deterministic overflow drops) and starved experts — through
    the same fixed-shape program."""

    S, D, E = 16, 8, 4

    def _gate(self, top_k=1, cf=1.0):
        paddle.seed(7)
        g = TopKGate(self.D, self.E, top_k=top_k, capacity_factor=cf)
        # zero gate projection -> uniform probs -> argmax tie-breaks to
        # expert 0 every round: all tokens collapse onto one expert
        g.weight.set_value(np.zeros((self.D, self.E), dtype=np.float32))
        return g

    def _x(self, G=2):
        rng = np.random.default_rng(3)
        return paddle.to_tensor(
            rng.standard_normal((G, self.S, self.D)).astype(np.float32))

    def test_all_tokens_one_expert_drops_deterministically(self):
        G = 2
        g = self._gate()
        dispatch, combine, aux, stats = g(self._x(G))
        C = moe_capacity(self.S, self.E, 1, 1.0)  # 4 slots
        kept = np.asarray(stats["expert_tokens"].numpy())
        assigned = np.asarray(stats["expert_assigned"].numpy())
        # every token asked for expert 0; only C per group fit
        np.testing.assert_array_equal(
            assigned, [G * self.S, 0, 0, 0])
        np.testing.assert_array_equal(kept, [G * C, 0, 0, 0])
        assert float(stats["dropped"].numpy()) == G * (self.S - C)
        # sequence-position priority: the FIRST C tokens of each group
        # survive, the rest drop — deterministic, not sampled
        d = np.asarray(dispatch.numpy())
        np.testing.assert_array_equal(
            d[:, :, 0, :].sum(axis=-1),
            np.repeat([[1.0] * C + [0.0] * (self.S - C)], G, axis=0))

    def test_starved_expert_zero_column_finite_grads(self):
        paddle.seed(9)
        m = MoEMLP(self.D, 2 * self.D, self.E, top_k=1,
                   capacity_factor=1.0)
        m.gate.weight.set_value(
            np.zeros((self.D, self.E), dtype=np.float32))
        x = self._x()
        x.stop_gradient = False
        y = m(x)
        assert y.shape == x.shape
        kept = np.asarray(m.last_stats["expert_tokens"].numpy())
        assert (kept[1:] == 0).all()  # experts 1..E-1 starved
        (y ** 2).mean().backward()
        for p in (m.gate.weight, m.w1, m.w2, x):
            assert p.grad is not None
            assert np.isfinite(np.asarray(p.grad.numpy())).all()
        # starved experts' banks get exactly-zero gradient
        g1 = np.asarray(m.w1.grad.numpy())
        assert (g1[1:] == 0.0).all() and np.abs(g1[0]).sum() > 0

    def test_routing_is_deterministic(self):
        g = self._gate(top_k=2, cf=1.25)
        x = self._x()
        d1, c1, _, _ = g(x)
        d2, c2, _, _ = g(x)
        np.testing.assert_array_equal(d1.numpy(), d2.numpy())
        np.testing.assert_array_equal(c1.numpy(), c2.numpy())


class TestDenseParity:
    """Acceptance gate: with uniform/forced gating the MoE layer is
    BITWISE-equal to the dense FFN it replaces (no +eps fudge anywhere
    on the combine path)."""

    D, FF, S = 8, 32, 16

    def _dense(self, x, w1, b1, w2, b2):
        h = paddle.matmul(x, paddle.to_tensor(w1)) + paddle.to_tensor(b1)
        h = F_act.gelu(h, approximate=True)
        return paddle.matmul(h, paddle.to_tensor(w2)) \
            + paddle.to_tensor(b2)

    def _weights(self):
        rng = np.random.default_rng(11)
        return (rng.standard_normal((self.D, self.FF)).astype("float32")
                * 0.05,
                rng.standard_normal(self.FF).astype("float32") * 0.05,
                rng.standard_normal((self.FF, self.D)).astype("float32")
                * 0.05,
                rng.standard_normal(self.D).astype("float32") * 0.05)

    def _x(self):
        rng = np.random.default_rng(13)
        return paddle.to_tensor(
            rng.standard_normal((2, self.S, self.D)).astype("float32"))

    def test_single_expert_is_exactly_dense(self):
        # E=1, k=1, cf=1.0: C=S, nothing drops, combine weight is 1.0
        paddle.seed(21)
        w1, b1, w2, b2 = self._weights()
        m = MoEMLP(self.D, self.FF, 1, top_k=1, capacity_factor=1.0)
        m.w1.set_value(w1[None]); m.b1.set_value(b1[None])
        m.w2.set_value(w2[None]); m.b2.set_value(b2[None])
        x = self._x()
        np.testing.assert_array_equal(
            m(x).numpy(), self._dense(x, w1, b1, w2, b2).numpy())

    def test_tied_experts_uniform_gate_exact(self):
        # E=4, k=2, zero gate, cf=E/k: every expert holds the SAME
        # weights, gates are uniform, capacity never binds — output is
        # bitwise the dense FFN and the aux loss is exactly 1.0
        paddle.seed(22)
        E = 4
        w1, b1, w2, b2 = self._weights()
        m = MoEMLP(self.D, self.FF, E, top_k=2, capacity_factor=E / 2)
        m.gate.weight.set_value(
            np.zeros((self.D, E), dtype=np.float32))
        m.w1.set_value(np.stack([w1] * E))
        m.b1.set_value(np.stack([b1] * E))
        m.w2.set_value(np.stack([w2] * E))
        m.b2.set_value(np.stack([b2] * E))
        x = self._x()
        np.testing.assert_array_equal(
            m(x).numpy(), self._dense(x, w1, b1, w2, b2).numpy())
        assert float(m.aux_loss.numpy()) == 1.0


class TestBitwiseReplay:
    """Satellite: the same batch through the captured executable twice
    is BITWISE identical — routing argmax/one_hot/cumsum are all
    deterministic ops, and replay launches one executable."""

    def test_same_batch_replays_bitwise(self):
        spmd.disable()
        cfg = GPTConfig.preset("gpt2-tiny-moe", vocab_size=V, n_layer=2,
                               seq_len=T, dropout=0.0, n_head=2,
                               d_model=32)
        paddle.seed(31)
        model = GPTForPretraining(GPTModel(cfg))
        # lr=0: parameters never move, so every step sees identical
        # state and the loss stream must be bitwise constant
        opt = paddle.optimizer.AdamW(0.0, parameters=model.parameters())
        crit = GPTPretrainingCriterion()
        rng = np.random.default_rng(4)
        toks = paddle.to_tensor(
            rng.integers(0, V, (B, T)).astype(np.int64))
        labels = paddle.to_tensor(np.roll(toks.numpy(), -1, 1))

        def step():
            with lazy.capture_guard(True), paddle.incubate.lazy_eval():
                loss = crit(model(toks), labels)
                aux = model.moe_aux_loss()
                loss = loss + aux
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

        losses = [step() for _ in range(6)]
        s0 = lazy.stats()
        losses += [step(), step()]
        s1 = lazy.stats()
        assert s1["captured_steps"] - s0["captured_steps"] == 2, \
            "the final pair did not run as captured replays"
        assert np.isfinite(losses).all()
        assert losses[-1] == losses[-2]  # bitwise, not allclose
        lazy.drop_plans("bitwise replay leg done")


class TestExpertLoadMetrics:
    """Satellite: per-expert token counts + drop fraction land in the
    'moe' registry scope as mergeable counters/hists, surfaced by
    moe.metrics.snapshot() (what fleet.stats() embeds) and the
    stats_dump 'expert load' section."""

    def test_publish_and_snapshot(self):
        _reg.reset("moe")
        _reg.gauge_drop("moe.drop_fraction")
        paddle.seed(41)
        m = MoEMLP(8, 16, 4, top_k=2, capacity_factor=1.25)
        assert moe_metrics.collect(m) is None  # no forward yet
        rng = np.random.default_rng(5)
        m(paddle.to_tensor(
            rng.standard_normal((2, 16, 8)).astype(np.float32)))
        snap = moe_metrics.publish(m)
        assert snap is not None and snap["expert_tokens"].shape == (4,)
        assert 0.0 <= snap["drop_fraction"] <= 1.0
        s = moe_metrics.snapshot()
        assert s is not None
        c = s["counters"]
        # conservation: every assigned token is kept or dropped
        assert c["tokens_kept"] + c["tokens_dropped"] \
            == c["tokens_assigned"]
        per_expert = sum(v for k, v in c.items()
                         if k.startswith("expert_tokens.e"))
        assert per_expert == c["tokens_kept"]
        assert s["hists"]["moe.expert_load_frac"]["count"] == 4
        assert s["drop_fraction"] == snap["drop_fraction"]

    def test_stats_dump_expert_load_section(self, capsys):
        sd = _tools_mod("stats_dump")
        snap = {
            "counters": {"moe.tokens_assigned": 100,
                         "moe.tokens_kept": 95,
                         "moe.tokens_dropped": 5,
                         "moe.expert_tokens.e0": 50,
                         "moe.expert_tokens.e1": 45},
            "gauges": {"moe.drop_fraction": 0.05},
            "hists": {"moe.expert_load_frac":
                      {"count": 4, "total_s": 1.0, "mean_ms": 250.0,
                       "buckets": {"19": 4}}},
        }
        sd._print_snapshot(snap)
        out = capsys.readouterr().out
        assert "expert load" in out
        assert "moe.drop_fraction" in out
        assert "mean_load=0.2500" in out
        # the load-fraction histogram is claimed by the moe section,
        # never misprinted as a latency
        assert "latency histograms" not in out


class TestEndpointGC:
    """Satellite: rendezvous-store GC — endpoint records deleted on
    clean teardown, superseded generations expired at publish time."""

    def _store(self):
        from paddle_tpu.distributed.store import TCPStore

        return TCPStore("127.0.0.1", 0, is_master=True, world_size=1)

    def test_delete_key_semantics(self):
        st = self._store()
        st.set("a", b"1")
        n0 = st.num_keys()
        assert st.delete_key("a") is True
        assert st.delete_key("a") is False  # already gone: no error
        assert st.num_keys() == n0 - 1

    def test_unpublish_endpoint(self):
        from paddle_tpu.distributed.fleet import elastic

        st = self._store()
        assert elastic.publish_endpoint(st, 0, "127.0.0.1", 1234, 1)
        key = elastic.endpoint_key(0)
        assert st.check(key) and st.check(f"{key}/gen")
        assert elastic.unpublish_endpoint(st, 0) is True
        assert not st.check(key) and not st.check(f"{key}/gen")
        # idempotent: a second teardown reports nothing-to-do
        assert elastic.unpublish_endpoint(st, 0) is False
        # and resolution no longer returns the dead incarnation
        assert elastic.resolve_endpoint(st, 0) is None

    def test_generation_gc_at_publish(self):
        from paddle_tpu.distributed.fleet import elastic

        st = self._store()
        for _ in range(3):
            assert elastic.publish_generation(st, 2)
        # gen 3 is live; gen 2 is kept for mid-read watchers; gen 1 is
        # superseded twice over and must be gone
        assert not st.check("elastic/members/1")
        assert not st.check("elastic/claim/1")
        assert st.check("elastic/members/2")
        assert st.check("elastic/members/3")
        assert elastic.publish_generation(st, 2)  # bump to 4
        assert not st.check("elastic/members/2")
        assert st.check("elastic/members/3")
        assert st.check("elastic/members/4")


_LEG: dict = {}


# ---- ISSUE 38: the served expert layer's grouped matmuls through a kernel ----

def _grouped_case(name):
    """(M, G, sizes) of one routing the kernel must survive."""
    rng = np.random.default_rng(38)
    if name == "even_groups_of_8":
        return 64, 8, [8] * 8
    if name == "uneven_with_empty_first_last_and_runs":
        return 96, 12, [0, 0, 5, 0, 0, 0, 11, 1, 0, 55, 24, 0]
    if name == "every_row_in_one_group":  # three chunks of the row tile
        return 96, 4, [0, 96, 0, 0]
    if name == "starts_not_multiples_of_8_or_16":
        return 64, 8, [3, 7, 5, 13, 9, 1, 15, 11]
    if name == "trailing_rows_no_group_owns":
        return 64, 8, [2, 0, 9, 4, 0, 6, 1, 0]
    if name == "held_16_of_128_experts":  # most rows belong elsewhere
        chosen = np.stack([rng.permutation(128)[:8] for _ in range(32)])
        return 256, 16, np.bincount(chosen[chosen < 16], minlength=16)
    raise KeyError(name)


GROUPED_CASES = ["even_groups_of_8", "uneven_with_empty_first_last_and_runs",
                 "every_row_in_one_group", "starts_not_multiples_of_8_or_16",
                 "trailing_rows_no_group_owns", "held_16_of_128_experts"]


def _grouped_operands(M, K, N, G, sizes, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((M, K)), dtype),
            jnp.asarray(rng.standard_normal((G, K, N)) * 0.1, dtype),
            jnp.asarray(np.asarray(sizes), jnp.int32))


class TestGroupedMatmulKernel:
    """`pallas_ops.grouped_matmul` through the Pallas interpreter against
    `lax.ragged_dot` on the same operands: the rows the groups own, to
    float32 round-off (both accumulate bf16 products in float32)."""

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("case", GROUPED_CASES)
    def test_kernel_matches_ragged_dot(self, case, dtype, monkeypatch):
        from paddle_tpu.ops import pallas_ops as po

        # tiles small enough that the toy weights are several tiles both
        # ways: the accumulation over k and the walk over column blocks run
        monkeypatch.setattr(po, "_GROUPED_TILE_BYTES",
                            128 * 128 * np.dtype(
                                "float32" if dtype == "float32"
                                else "float16").itemsize)
        M, G, sizes = _grouped_case(case)
        K, N = 256, 384
        route, (tm, tk, tn), _ = po._grouped_plan(M, K, N, G, dtype)
        assert route == "kernel" and tm == 32 and tk < K and tn < N
        x, w, g = _grouped_operands(M, K, N, G, sizes, dtype)
        got = np.asarray(po.grouped_matmul(x, w, g, kernel="interpret"))
        ref = np.asarray(po.grouped_matmul(x, w, g, kernel="xla"))
        owned = int(np.sum(sizes))
        assert got.shape == ref.shape == (M, N) and got.dtype == np.float32
        np.testing.assert_allclose(got[:owned], ref[:owned], rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_shapes_that_are_not_whole_tiles(self, dtype):
        """K and N that are not whole 128-lane tiles: the interpreter takes
        them whole, a compiled plan refuses, loudly (the engine's selection
        counts a fallback and says why)."""
        from paddle_tpu.ops import pallas_ops as po

        M, K, N, G = 48, 96, 200, 6
        assert po._grouped_plan(M, K, N, G, dtype, compiled=False)[:2] == (
            "kernel", (32, K, N))
        route, _, why = po._grouped_plan(M, K, N, G, dtype)
        assert route == "refused" and "128-lane" in why
        assert po._grouped_plan(40, 128, 128, G, dtype)[0] == "refused"
        sizes = [5, 0, 17, 1, 9, 12]
        x, w, g = _grouped_operands(M, K, N, G, sizes, dtype)
        got = np.asarray(po.grouped_matmul(x, w, g, kernel="interpret"))
        ref = np.asarray(po.grouped_matmul(x, w, g, kernel="xla"))
        np.testing.assert_allclose(got[:44], ref[:44], rtol=1e-5, atol=1e-5)
        before = _reg.counters("serving")["kernel.fallbacks"]
        kind, reason = po.select_grouped_kernel(
            "pallas", shapes=[(M, K, N, G)], dtype=dtype)
        assert kind == "xla" and "128-lane" in reason
        assert _reg.counters("serving")["kernel.fallbacks"] == before + 1
        ev = _explain.events(kind="kernel_fallback")[-1]
        assert ev["op"] == "grouped_matmul" and "128-lane" in ev["why"]

    def test_a_prompts_rows_stay_with_ragged_dot(self):
        """More than 32 rows a group is `lax.ragged_dot`'s regime whatever
        kind the engine resolved to: by design, so nothing is counted."""
        import jax

        from paddle_tpu.ops import pallas_ops as po

        x, w, g = _grouped_operands(512, 128, 128, 4, [100, 200, 12, 150],
                                    "float32")
        before = _reg.counters("serving")["kernel.fallbacks"]
        text = str(jax.make_jaxpr(lambda *a: po.grouped_matmul(
            *a, kernel="interpret"))(x, w, g))
        assert "ragged_dot" in text and "pallas_call" not in text
        assert po.select_grouped_kernel(
            "pallas", shapes=[(512, 128, 128, 4)], dtype="float32")[0] == "xla"
        assert _reg.counters("serving")["kernel.fallbacks"] == before
        with pytest.raises(ValueError, match="unknown grouped-matmul"):
            po.grouped_matmul(x, w, g, kernel="mosaic")


# the three expert cells' decode shapes (rows, K, N, held experts) with the
# tiles PERF.md's PR 38 entry names, and their prompts' shapes
GROUPED_DECODE = {
    "sdar_gate_up": ((1024, 2048, 1536, 128), (32, 1024, 1536)),
    "sdar_down": ((1024, 768, 2048, 128), (32, 768, 2048)),
    "commanda_gate_up": ((256, 4096, 8192, 16), (32, 256, 8192)),
    "commanda_down": ((256, 4096, 4096, 16), (32, 512, 4096)),
    "xing4_gate_up": ((128, 3584, 2048, 64), (32, 896, 2048)),
    "xing4_down": ((128, 1024, 3584, 64), (32, 512, 3584)),
}
GROUPED_PREFILL = {
    "sdar_bucket_1536": (12288, 2048, 1536, 128),
    "sdar_bucket_4096": (32768, 768, 2048, 128),
    "commanda_chunk_of_1024": (8192, 4096, 8192, 16),
    "xing4_bucket_1024": (4096, 3584, 2048, 64),
    "xing4_bucket_2048": (8192, 1024, 3584, 64),
}


class TestGroupedPlan:
    @pytest.mark.parametrize("cell", list(GROUPED_DECODE))
    def test_decode_shapes_take_the_kernel(self, cell):
        from paddle_tpu.ops import pallas_ops as po

        shape, tiles = GROUPED_DECODE[cell]
        route, got, why = po._grouped_plan(*shape, "bfloat16")
        assert (route, got, why) == ("kernel", tiles, "tileable")
        M, K, N, _ = shape
        _, tk, tn = tiles
        assert K % tk == 0 and N % tn == 0 and tk % 128 == 0
        assert tk * tn * 2 <= po._GROUPED_TILE_BYTES
        assert po._grouped_vmem_bytes(M, K, N, tiles, "bfloat16") \
            <= po._GROUPED_VMEM_BUDGET

    @pytest.mark.parametrize("cell", list(GROUPED_PREFILL))
    def test_prefill_shapes_keep_ragged_dot(self, cell):
        from paddle_tpu.ops import pallas_ops as po

        route, _, why = po._grouped_plan(*GROUPED_PREFILL[cell], "bfloat16")
        assert route == "xla" and "a group" in why

    def test_the_plan_reads_shapes_alone(self):
        """No argument of the plan is a name, a flag of a model or the
        environment's: four ints, a dtype and who compiles."""
        import inspect

        from paddle_tpu.ops import pallas_ops as po

        assert list(inspect.signature(po._grouped_plan).parameters) == [
            "M", "K", "N", "G", "dtype", "compiled"]
        src = inspect.getsource(po._grouped_plan)
        assert "environ" not in src and "getattr" not in src
        # too large for VMEM: refused with the sizes, not attempted
        route, _, why = po._grouped_plan(4096, 8192, 8192, 128, "bfloat16")
        assert route == "refused" and "MiB of VMEM" in why


def _dropless(kind):
    """The three cells' routers at toy size, float32."""
    from paddle_tpu.nn.moe.dropless import DroplessMoE

    paddle.seed(38)
    if kind == "xing4":  # sigmoid + selection bias, scaling 2, one shared
        return DroplessMoE(48, 24, 8, 2, n_shared=1,
                           routed_scaling_factor=2.0)
    if kind == "commanda":  # a share of the experts, 4 shared averaged,
        # a prompt's tokens a chunk at a time
        return DroplessMoE(48, 24, 16, 4, n_shared=4, select_bias=False,
                           experts_held=(0, 4), shared_combine="average",
                           rows_at_a_time=8)
    return DroplessMoE(48, 24, 16, 2, n_shared=0, select_bias=False,
                       router="softmax")


@pytest.mark.parametrize("kind", ["xing4", "commanda", "sdar"])
def test_dropless_forward_through_the_kernel(kind):
    """`DroplessMoE.forward` with the kernel forced to the interpreter
    against the `lax.ragged_dot` route: float32 round-off, decode rows and
    a prompt's (with padding routed nowhere)."""
    layer = _dropless(kind)
    rng = np.random.default_rng(0)
    for B, T in ((4, 1), (1, 16)):
        x = paddle.to_tensor(rng.standard_normal((B, T, 48)).astype(
            np.float32))
        valid = paddle.to_tensor(np.arange(B * T).reshape(B, T) < B * T - 3)
        for v in (None, valid):
            ref = layer(x, valid=v).numpy()
            hit = int(layer.last_experts_hit)
            got = layer(x, valid=v, kernel="interpret").numpy()
            assert int(layer.last_experts_hit) == hit
            assert np.isfinite(got).all()
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    assert layer.grouped_shapes(4) == [
        (4 * layer.top_k, 48, 48, layer.experts_held[1]),
        (4 * layer.top_k, 24, 48, layer.experts_held[1])]


@pytest.mark.parametrize("family", ["sdar", "xing4"])
def test_engine_says_which_grouped_matmul_it_ran(family):
    """The gauge, its reason and `serving.moe_kernel_layer_steps` read what
    the engine did: on the CPU an engine resolves to `xla` (counter 0,
    nothing counted as a fallback); asked for pallas it runs the kernel's
    body through the interpreter in every expert layer of every step, and
    serves the same tokens."""
    from paddle_tpu.models import (SdarMoeConfig, SdarMoeModel, Xing4Config,
                                   Xing4Model)
    from paddle_tpu.serving.engine import GenerationEngine

    served = {}
    for request, kind in ((None, "xla"), ("pallas", "interpret")):
        paddle.seed(0)
        model = SdarMoeModel(SdarMoeConfig.preset("tiny")) \
            if family == "sdar" else Xing4Model(Xing4Config.preset("tiny"))
        model.eval()
        eng = GenerationEngine(model, max_batch_size=2, buckets=(16,),
                               max_seq_len=64, rng_seed=0,
                               paged_kernel=request)
        c0 = dict(_reg.counters("serving"))
        st = eng.stats()
        assert st["moe_grouped_kernel"] == kind, st
        assert _reg.gauge("serving.moe_grouped_kernel") == kind
        assert ("interpreter" if request else "resolved to xla") \
            in st["moe_grouped_kernel_reason"]
        first = eng.prefill(0, list(range(3, 12)))
        steps = [eng.decode_step() for _ in range(4)]
        c1 = _reg.counters("serving")
        moved = {k: c1[k] - c0.get(k, 0) for k in (
            "moe_layer_steps", "moe_kernel_layer_steps", "kernel.fallbacks")}
        assert moved["moe_layer_steps"] > 0 and moved["kernel.fallbacks"] == 0
        assert moved["moe_kernel_layer_steps"] == (
            moved["moe_layer_steps"] if request else 0)
        served[kind] = repr((first, [np.asarray(s).tolist()
                                     if not isinstance(s, list) else s
                                     for s in steps]))
    assert served["xla"] == served["interpret"]


def _batch(rng):
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    return (spmd.shard_batch(paddle.to_tensor(toks)),
            spmd.shard_batch(paddle.to_tensor(np.roll(toks, -1, 1))))


def _moe_model():
    cfg = GPTConfig.preset("gpt2-tiny-moe", vocab_size=V, n_layer=2,
                           seq_len=T, dropout=0.0, n_head=2, d_model=32)
    paddle.seed(123)
    model = GPTForPretraining(GPTModel(cfg))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    return model, opt, GPTPretrainingCriterion()


def _moe_steps(model, opt, crit, rng, n):
    def step():
        toks, labels = _batch(rng)
        with lazy.capture_guard(True), paddle.incubate.lazy_eval():
            loss = crit(model(toks), labels)
            loss = loss + model.moe_aux_loss()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return float(loss)

    return [step() for _ in range(n)]


def _init_moe_fleet(ep):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "mp_degree": 1, "pp_degree": 1,
        "sharding_degree": 1, "ep_degree": ep, "use_spmd": True}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _moe_leg():
    """ONE gpt2-tiny-moe dp=2 x ep=2 leg: N_WARM warmup steps, then an
    N_STEADY gate window with VARYING batches — the acceptance gate is
    zero compiles across 20 steps of changing routing decisions."""
    if _LEG:
        return _LEG
    hcg = _init_moe_fleet(ep=2)
    mesh = hcg.spmd_mesh()
    assert "ep" in mesh.axis_names
    model, opt, crit = _moe_model()
    model = fleet.distributed_model(model)
    rng = np.random.default_rng(0)
    warm = _moe_steps(model, opt, crit, rng, N_WARM)
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    steady = _moe_steps(model, opt, crit, rng, N_STEADY)
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    deltas = {k: c1[k] - c0.get(k, 0) for k in c1}
    deltas.update({k: s1[k] - s0[k] for k in s1})
    _LEG.update(model=model, opt=opt, crit=crit, losses=warm + steady,
                deltas=deltas, desc=spmd.describe_plans())
    return _LEG


class TestExpertParallelSPMD:
    """Acceptance gate: the MoE train step is ONE compiled executable
    under dp=2 x ep=2 — zero post-warmup compiles across N_STEADY steps
    with varying (data-dependent) routing."""

    def test_zero_recompiles_despite_routing(self):
        leg = _moe_leg()
        d = leg["deltas"]
        assert np.isfinite(leg["losses"]).all()
        assert d["step_compiles"] == 0
        assert d["nodes_built"] == 0
        assert d["captured_steps"] == N_STEADY
        assert d["capture_fallbacks"] == 0
        assert d["python_collectives"] == 0
        assert d["donated_steps"] == N_STEADY

    def test_expert_banks_shard_over_ep(self):
        leg = _moe_leg()
        desc = leg["desc"]
        assert desc["mesh"]["axes"].get("ep") == 2
        plans = [p for p in desc["plans"] if p["spmd"]]
        assert len(plans) == 1
        ep_leaves = [lf for lf in plans[0]["leaves"]
                     if lf.get("expert_membership") == "sharded"]
        assert ep_leaves, "no expert bank sharded over 'ep'"
        # banks AND their optimizer slots ride the ep axis (donation
        # keeps them in-place)
        assert any(lf.get("donated") for lf in ep_leaves)

    def test_expert_load_publishes_from_leg(self):
        leg = _moe_leg()
        _reg.reset("moe")
        snap = moe_metrics.publish(leg["model"])
        assert snap is not None
        assert snap["expert_tokens"].sum() > 0
        assert moe_metrics.snapshot() is not None


class TestShardingLintEP:
    """Satellite: tools/sharding_lint.py knows the 'ep' axis — expert
    coverage on an ep>1 mesh and ep-specific donation wording."""

    def _desc(self, leaves):
        return {"mesh": {"axes": {"dp": 2, "ep": 2, "mp": 1}},
                "plans": [{"spmd": True, "first_op": "embedding",
                           "donate_confirmed": True, "leaves": leaves}]}

    def test_flags_missing_ep_coverage(self):
        slint = _tools_mod("sharding_lint")
        leaf = {"class": 0, "shape": [4, 32, 128], "dtype": "float32",
                "bytes": 4 * 32 * 128 * 4, "spec": [None, None, None],
                "slot_flagged": False, "carried": False, "donated": False}
        probs = slint.lint(self._desc([leaf]))
        assert any("expert-sharded" in p and "replicated on every ep"
                   in p for p in probs)
        # an ep-sharded bank satisfies coverage
        ok = dict(leaf, spec=["ep", None, None])
        assert slint.lint(self._desc([ok])) == []

    def test_ep_donation_wording(self):
        slint = _tools_mod("sharding_lint")
        leaf = {"class": 0, "shape": [4, 32, 128], "dtype": "float32",
                "bytes": 4 * 32 * 128 * 4, "spec": ["ep", None, None],
                "slot_flagged": True, "carried": True, "donated": False}
        probs = slint.lint(self._desc([leaf]))
        assert any("expert-sharded (ep)" in p and "[E/ep]" in p
                   for p in probs)
        assert slint.lint(
            self._desc([dict(leaf, donated=True)])) == []

    def test_live_leg_plan_is_clean(self):
        slint = _tools_mod("sharding_lint")
        assert slint.lint(_moe_leg()["desc"]) == []


class TestEpParity:
    """Acceptance gate: ep=2 matches ep=1 on the same seed/data — the
    all-to-all placement changes WHERE experts run, not what they
    compute. Runs LAST: re-initializing the fleet at ep=1 drops the
    shared leg's mesh and plans."""

    def test_ep2_matches_ep1(self):
        losses2 = _moe_leg()["losses"]
        n = 12
        _init_moe_fleet(ep=1)
        model, opt, crit = _moe_model()
        model = fleet.distributed_model(model)
        rng = np.random.default_rng(0)
        losses1 = _moe_steps(model, opt, crit, rng, n)
        np.testing.assert_allclose(losses2[:n], losses1, rtol=2e-2,
                                   atol=1e-4)
