"""Pallas paged-attention kernel family (ISSUE 14).

Interpreter-mode parity on CPU: the REAL kernel body (scalar-prefetched
block tables, per-block online-softmax folding, garbage-block-0
semantics) runs through ``pl.pallas_call(interpret=True)`` and must match
the PR 9 XLA gather oracle within the pinned per-dtype tolerance
(``pallas_ops.PAGED_PARITY_TOL`` — fp32 differs by reduction order only,
bf16 additionally by where probabilities are rounded). Covers:

  * seq_lens straddling block boundaries (bs-1 / bs / bs+1 / mid-block);
  * inactive lanes aimed at reserved garbage block 0 (finite output,
    live lanes unperturbed);
  * the verify-span variant's causal intra-span masking (row t provably
    independent of keys at positions > q_offset + t);
  * ragged batches sharing physical blocks (prefix-style aliasing);
  * end-to-end greedy/sampled serving-token parity across kernel
    choices, including the spec-decode verify span;
  * the zero-post-warmup-compile gate with the kernel layer active (the
    PR 8 replay fingerprint is stable under kernel selection);
  * the ``kernel_mismatch`` fault provably trips the parity gate;
  * the span walk of PR 33 (a program copies and folds G blocks for all
    heads): lengths on and off block and span edges against the gather
    oracle AND a float64 numpy oracle, every head geometry the lowering
    test compiles, one block a program for a large block;
  * grouped queries (Hq/Hkv in {1, 4, 16}) and a window layer's ring that
    has wrapped (PR 35), against the gather route and a float64 oracle;
  * the prompt span's kernel `flash_prefill` (PR 36): cold, behind a cached
    prefix, a chunk that ends mid-block, a prompt shorter than its bucket,
    over several query blocks and key spans, against both oracles; and the
    engine's one-shot, chunked and prefix-hit prefills through it, token
    for token.

The parity cases run on a 4-D pool passed to the public op (merged on
entry) and on the engine's merged pool (PR 28). The compiled kernel is
checked off-chip by tests/test_tpu_lowering.py and on the chip by
chip_smoke.py.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import explainer, registry
from paddle_tpu.testing import faults

VOCAB = 96


def _build_model(seed=11):
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=48,
                    seq_len=64, initializer_range=0.35)
    return GPTForPretraining(GPTModel(cfg))


def _case(B, T, H, Dh, Nb, bs, M, dtype=jnp.float32, seed=0, form="4d"):
    """Random pools + per-lane tables over distinct nonzero blocks. The
    pools come 4-D ``[Nb, bs, H, Dh]`` (what the public op still takes,
    merged on entry) or in the engine's device form ``[Nb, bs, H*Dh]``."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, Dh)), dtype)
    kp = jnp.asarray(rng.standard_normal((Nb, bs, H, Dh)), dtype)
    vp = jnp.asarray(rng.standard_normal((Nb, bs, H, Dh)), dtype)
    if form == "merged":
        kp, vp = kp.reshape(Nb, bs, H * Dh), vp.reshape(Nb, bs, H * Dh)
    ids = rng.permutation(np.arange(1, Nb))[:B * M].reshape(B, M)
    bt = jnp.asarray(ids, jnp.int32)
    return q, kp, vp, bt


def _parity(q, kp, vp, bt, sl, qo):
    sl = jnp.asarray(sl, jnp.int32)
    qo = jnp.asarray(qo, jnp.int32)
    fused = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                       kernel="interpret")
    ref = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo, kernel="xla")
    atol, rtol = pallas_ops.PAGED_PARITY_TOL[jnp.dtype(q.dtype).name]
    np.testing.assert_allclose(
        np.asarray(fused, np.float32), np.asarray(ref, np.float32),
        atol=atol, rtol=rtol)
    return fused


@pytest.mark.parametrize("form", ["4d", "merged"])
class TestKernelParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_decode_straddles_block_boundaries(self, dtype, form):
        # bs=4: valid lengths 3 / 4 / 5 / 10 sit just under, exactly on,
        # just over and mid-way across block boundaries; T=1 decode rows
        # at the cursor (the engine's q_offset = seq_len - 1)
        q, kp, vp, bt = _case(4, 1, 2, 16, 16, 4, 3, dtype=dtype,
                              form=form)
        sl = [3, 4, 5, 10]
        qo = [s - 1 for s in sl]
        _parity(q, kp, vp, bt, sl, qo)

    def test_inactive_lane_on_garbage_block0(self, form):
        # lane 1 is released: zeroed table row, seq_len 1, cursor 0 —
        # every read lands in reserved block 0. Output must be finite
        # (denominator never 0), parity must hold, and the dead lane
        # must not perturb the live lanes' rows.
        q, kp, vp, bt = _case(3, 1, 2, 16, 12, 4, 3, form=form)
        bt = bt.at[1].set(0)
        sl, qo = [9, 1, 6], [8, 0, 5]
        out = _parity(q, kp, vp, bt, sl, qo)
        assert bool(jnp.isfinite(out).all())
        solo = pallas_ops.paged_attention(
            q[::2], kp, vp, bt[::2], jnp.asarray(sl[::2], jnp.int32),
            jnp.asarray(qo[::2], jnp.int32), kernel="interpret")
        np.testing.assert_array_equal(np.asarray(out[::2], np.float32),
                                      np.asarray(solo, np.float32))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_verify_span_causal_mask(self, dtype, form):
        # the [B, K+1] verify span: row t may read positions <= qo + t.
        # Parity first; then perturb the pool rows holding positions
        # BEYOND qo + 1 — span rows 0 and 1 must be bitwise unchanged
        # (causality), while some later row must change (the probe is
        # live, not vacuous).
        B, T, bs, M = 2, 4, 4, 4
        q, kp, vp, bt = _case(B, T, 2, 16, 16, bs, M, dtype=dtype,
                              form=form)
        cur = [5, 9]
        sl = [c + T for c in cur]
        _parity(q, kp, vp, bt, sl, cur)
        base = pallas_ops.paged_attention(
            q, kp, vp, bt, jnp.asarray(sl, jnp.int32),
            jnp.asarray(cur, jnp.int32), kernel="interpret")
        kp2, vp2 = kp, vp
        for b in range(B):
            for posn in range(cur[b] + 2, sl[b]):
                blk = int(bt[b, posn // bs])
                kp2 = kp2.at[blk, posn % bs].add(jnp.asarray(3.0, dtype))
                vp2 = vp2.at[blk, posn % bs].add(jnp.asarray(3.0, dtype))
        bumped = pallas_ops.paged_attention(
            q, kp2, vp2, bt, jnp.asarray(sl, jnp.int32),
            jnp.asarray(cur, jnp.int32), kernel="interpret")
        np.testing.assert_array_equal(
            np.asarray(base[:, :2], np.float32),
            np.asarray(bumped[:, :2], np.float32))
        assert not np.array_equal(np.asarray(base[:, 3], np.float32),
                                  np.asarray(bumped[:, 3], np.float32))

    def test_ragged_batch_with_shared_blocks(self, form):
        # prefix-style aliasing: every lane's FIRST logical block is the
        # same physical block (a shared system prompt), lengths ragged
        # across the batch; parity must hold with the aliased reads
        q, kp, vp, bt = _case(4, 1, 2, 16, 20, 4, 4, form=form)
        bt = bt.at[:, 0].set(int(bt[0, 0]))
        sl = [2, 6, 11, 16]
        qo = [s - 1 for s in sl]
        _parity(q, kp, vp, bt, sl, qo)


class TestHeadsOnLaneTiles:
    """The kernel takes the merged axis in groups of whole 128-lane tiles
    holding whole heads and meets a group's heads in one dot (head h's
    query on row h, on its own lanes, zeros on the rest; PR 33), never by
    a lane shift. Geometries on every side of that choice: heads that
    share a tile (64-, 32-, 16-wide), heads that own theirs (128), a
    head count that does not fill whole tiles (3 x 64) and a width that
    divides nothing (24), each against the gather oracle."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("H,Dh,lanes", [
        (2, 64, 128), (4, 64, 256), (4, 32, 128), (8, 16, 128),
        (2, 128, 256), (3, 64, 192), (2, 24, 48)])
    def test_parity_and_head_groups(self, H, Dh, lanes, dtype):
        assert pallas_ops._paged_group_lanes(H, Dh) == lanes
        B, T, bs, M = 3, 3, 4, 3
        q, kp, vp, bt = _case(B, T, H, Dh, 12, bs, M, dtype=dtype,
                              form="merged", seed=H * Dh)
        bt = bt.at[1].set(0)  # a dead lane on the garbage block
        cur = [5, 0, 8]
        out = _parity(q, kp, vp, bt, [c + T for c in cur], cur)
        assert out.shape == (B, T, H, Dh)
        assert bool(jnp.isfinite(out).all())

    def test_tile_mates_do_not_leak_into_each_other(self):
        # heads 0 and 1 share a lane tile: blowing up head 1's keys and
        # values must leave head 0's output bitwise unchanged
        B, T, H, Dh, bs, M = 2, 1, 2, 64, 4, 3
        q, kp, vp, bt = _case(B, T, H, Dh, 10, bs, M, form="merged")
        sl = jnp.asarray([7, 10], jnp.int32)
        qo = sl - 1
        base = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                          kernel="interpret")
        kp2 = kp.at[:, :, Dh:].multiply(-7.0)
        vp2 = vp.at[:, :, Dh:].add(100.0)
        got = pallas_ops.paged_attention(q, kp2, vp2, bt, sl, qo,
                                         kernel="interpret")
        np.testing.assert_array_equal(np.asarray(base[:, :, 0]),
                                      np.asarray(got[:, :, 0]))
        assert not np.array_equal(np.asarray(base[:, :, 1]),
                                  np.asarray(got[:, :, 1]))


def _oracle64(q, kp, vp, bt, sl, qo):
    """Float64 numpy attention over each slot's gathered rows: key j is
    valid for row t iff j <= qo + t and j < sl."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    bt, sl, qo = (np.asarray(a) for a in (bt, sl, qo))
    B, T, H, Dh = q.shape
    out = np.zeros_like(q)
    for b in range(B):
        k = kp[bt[b]].reshape(-1, H, Dh)
        v = vp[bt[b]].reshape(-1, H, Dh)
        j = np.arange(k.shape[0])
        for t in range(T):
            ok = (j <= qo[b] + t) & (j < sl[b])
            s = np.einsum("hd,jhd->hj", q[b, t], k) * Dh ** -0.5
            s = np.where(ok[None], s, -np.inf)
            p = np.exp(s - s.max(axis=1, keepdims=True))
            out[b, t] = np.einsum("hj,jhd->hd",
                                  p / p.sum(axis=1, keepdims=True), v)
    return out


def _against_both(q, kp, vp, bt, sl, qo):
    """Interpret route against the gather route and the float64 oracle;
    returns the worst |fused - oracle|."""
    out = _parity(q, kp, vp, bt, sl, qo)
    want = _oracle64(q, kp, vp, bt, sl, qo)
    atol, rtol = pallas_ops.PAGED_PARITY_TOL[jnp.dtype(q.dtype).name]
    got = np.asarray(out, np.float64)
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol)
    return float(np.abs(got - want).max())


class TestSpanWalk:
    """PR 33: program (b, j) copies the live blocks of its span of G table
    columns itself, one span ahead, and folds them for all heads in two
    dots. A small span (2 blocks of 4 keys, set in the test) puts lengths
    on every side of a block's and a span's edge in a table of 3 spans."""

    @pytest.fixture
    def span8(self, monkeypatch):
        monkeypatch.setattr(pallas_ops, "_PAGED_MAX_SPAN_KEYS", 8)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T", [1, 5])
    @pytest.mark.parametrize("length", [1, 6, 8, 12, 13, 16, 21, 24])
    def test_lengths_against_both_oracles(self, span8, length, T, dtype):
        # 1 key; mid-block; a span's edge; a block's edge mid-span;
        # mid-block mid-span; the second span's edge; the last span; the
        # full table. Slot 1 is an inactive lane (zeroed table row), slot 2
        # a neighbour whose copies run ahead of and behind the probe's.
        bs, M = 4, 6
        assert pallas_ops._paged_plan(bs, 2, 64, dtype, T, M)[0] == 2
        q, kp, vp, bt = _case(3, T, 2, 64, 20, bs, M, dtype=dtype,
                              form="merged", seed=length + T)
        bt = bt.at[1].set(0)
        sl = [length, 1, 11]
        qo = [max(n - T, 0) for n in sl]  # T = 5: offsets mid-span
        _against_both(q, kp, vp, bt, sl, qo)
        out = pallas_ops.paged_attention(
            q, kp, vp, bt, jnp.asarray(sl, jnp.int32),
            jnp.asarray(qo, jnp.int32), kernel="interpret")
        assert bool(jnp.isfinite(out).all())

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T", [1, 5])
    @pytest.mark.parametrize("H,Dh", [(32, 64), (16, 128), (12, 80)])
    def test_head_geometries_at_the_real_span(self, H, Dh, T, dtype):
        # block 16, 20 table columns: two programs a slot at 16 blocks a
        # program (three at 8: a float32 row of 2048 lanes is twice the
        # bytes, and the budget halves the span); lengths end in the first
        # span, on a span's edge and in the last; q_offsets of the verify
        # span mid-span
        bs, M = 16, 20
        wide_f32 = dtype == jnp.float32 and H * Dh == 2048
        assert pallas_ops._paged_plan(bs, H, Dh, dtype, T, M)[0] \
            == (8 if wide_f32 else 16)
        q, kp, vp, bt = _case(3, T, H, Dh, 61, bs, M, dtype=dtype,
                              form="merged", seed=H + T)
        sl = [77, 256, 311]
        _against_both(q, kp, vp, bt, sl, [n - T for n in sl])

    def test_one_block_a_program_for_a_large_block(self):
        # 64 heads x 256 x 32 rows of float32: a 2 MiB block, so the
        # budget leaves a program one block (and refuses the 4 MiB one)
        H, Dh, bs, M = 64, 256, 32, 3
        assert pallas_ops._paged_plan(bs, H, Dh, jnp.float32, 1, M)[0] == 1
        assert pallas_ops._paged_plan(2 * bs, H, Dh, jnp.float32)[0] == 0
        q, kp, vp, bt = _case(2, 1, H, Dh, 7, bs, M, form="merged", seed=9)
        _against_both(q, kp, vp, bt, [40, 96], [39, 95])

    def test_error_at_the_chat_cells_geometry_is_no_larger_than_before(self):
        # gpt3-1.3b's pool (32 x 64 heads, block 16, bf16, 128 table
        # columns, 8 programs a slot) at lengths the chat cell holds.
        # PARENT: the 16-keys-a-program body of PR 32 (commit ac7b8f9)
        # compiled on the chip (TPU v5 lite), this case, this oracle (my
        # chip run, PR 33). The new body gave 0.000954921 there and gives it
        # here: its dots take the pool's dtype, so the interpreter rounds
        # what the chip rounds.
        PARENT = 0.0012225186840707503
        lens = [137, 528, 1391]
        q, kp, vp, bt = _case(3, 1, 32, 64, 3 * 128 + 1, 16, 128,
                              dtype=jnp.bfloat16, form="merged", seed=33)
        need = -(-np.asarray(lens) // 16)
        bt = jnp.where(jnp.arange(128)[None] < need[:, None], bt, 0)
        assert pallas_ops._paged_plan(16, 32, 64, jnp.bfloat16, 1, 128)[0] \
            == 16
        worst = _against_both(q, kp, vp, bt, lens, [n - 1 for n in lens])
        assert worst <= PARENT, (worst, PARENT)


class TestGroupedQueriesAndWindow:
    """PR 35: a pool whose row holds Hkv heads read by Hq = R x Hkv query
    heads (a key/value head's R queries meet a span in one dot), and a
    window layer's ring: position p in ring block (p // bs) % ring, the
    walk from the block of the oldest key the query may see, the oldest
    block's rows that fell out masked. The interpreter's kernel body
    against the gather route AND a float64 oracle that knows neither
    groups nor rings (it is handed each slot's keys in position order)."""

    @staticmethod
    def _ring_case(Hkv, R, Dh, window, lens, bs=4, dtype=jnp.float32,
                   seed=0):
        """Pools filled a row at a time, position by position, as a slot's
        decode steps fill them: a ring overwrites itself; rows nothing
        wrote hold noise (the mask must keep them out)."""
        from paddle_tpu.ops import kv_pool

        rng = np.random.default_rng(seed)
        B, W = len(lens), Hkv * Dh
        if window is None:
            M = -(-max(lens) // bs) + 1
            tables = 1 + np.arange(B * M, dtype=np.int32).reshape(B, M)
        else:
            M = kv_pool.ring_blocks(window, bs)
            tables = kv_pool.ring_table(B, M)
        kp = rng.standard_normal((1 + B * M, bs, W))
        vp = rng.standard_normal((1 + B * M, bs, W))
        keys = []  # per slot: the rows by position, for the oracle
        for b, n in enumerate(lens):
            rows = rng.standard_normal((2, n, W))
            for p in range(n):
                col = p // bs if window is None else (p // bs) % M
                kp[tables[b, col], p % bs] = rows[0, p]
                vp[tables[b, col], p % bs] = rows[1, p]
            keys.append(rows)
        q = jnp.asarray(rng.standard_normal((B, 1, Hkv * R, Dh)), dtype)
        return (q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
                jnp.asarray(tables), keys)

    @staticmethod
    def _oracle(q, keys, window, Hkv, dtype):
        q = np.asarray(q, np.float64)
        B, _, Hq, Dh = q.shape
        out = np.zeros_like(q)
        for b, rows in enumerate(keys):
            k, v = (np.asarray(jnp.asarray(r, dtype), np.float64).reshape(
                -1, Hkv, Dh) for r in rows)
            lo = 0 if window is None else max(0, k.shape[0] - window)
            for h in range(Hq):
                g = h // (Hq // Hkv)
                s = k[lo:, g] @ q[b, 0, h] * Dh ** -0.5
                p = np.exp(s - s.max())
                out[b, 0, h] = (p / p.sum()) @ v[lo:, g]
        return out

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("window", [None, 8, 64])  # none, < len, > len
    @pytest.mark.parametrize("Hkv,R,Dh", [(2, 1, 64), (2, 4, 32),
                                          (1, 16, 128), (4, 4, 64)])
    def test_against_both_oracles(self, Hkv, R, Dh, window, dtype):
        lens = [5, 30, 1, 23]  # 30 rows wrap a ring of 3 blocks of 4 twice
        q, kp, vp, bt, keys = self._ring_case(Hkv, R, Dh, window, lens,
                                              dtype=dtype)
        sl = jnp.asarray(lens, jnp.int32)
        got = {k: np.asarray(pallas_ops.paged_attention(
            q, kp, vp, bt, sl, sl - 1, kernel=k, window=window), np.float64)
            for k in ("interpret", "xla")}
        want = self._oracle(q, keys, window, Hkv, dtype)
        atol, rtol = pallas_ops.PAGED_PARITY_TOL[jnp.dtype(dtype).name]
        for k in got:
            np.testing.assert_allclose(got[k], want, atol=atol, rtol=rtol,
                                       err_msg=k)

    def test_plan_and_names(self):
        """At the cell's geometry (8 key/value heads of 128, 16 queries
        each) a group is 4 key/value heads: 64 query rows against 512
        lanes, 2 groups, 512 keys a program (measured, PERF.md PR 35) — and
        grouped queries leave the multi-head plan where it was."""
        assert pallas_ops._paged_plan(16, 8, 128, jnp.bfloat16, 1, 560,
                                      16) == (32, 512, 64)
        assert pallas_ops._paged_plan(16, 8, 128, jnp.bfloat16, 1, 257,
                                      16) == (32, 512, 64)
        assert pallas_ops.paged_keys_per_program(
            16, 8, 128, jnp.bfloat16, 560, 16) == 512
        assert pallas_ops._paged_plan(16, 32, 64, jnp.bfloat16, 1, 128) \
            == (16, 2048, 32)
        assert pallas_ops._paged_plan(16, 4, 64, jnp.bfloat16, 1, 64, 4)[1:] \
            == (256, 16)  # all four 64-wide heads: 4 x 4 rows
        from paddle_tpu.profiler import spans
        assert {"paged_attention", "paged_attention_window"} <= set(
            spans.KERNELS)

    def test_a_ring_takes_one_row_a_slot_and_no_mesh(self):
        q, kp, vp, bt, _ = self._ring_case(2, 2, 32, 8, [9, 3])
        sl = jnp.asarray([9, 3], jnp.int32)
        with pytest.raises(TypeError, match="one query row"):
            pallas_ops.paged_attention(
                jnp.concatenate([q, q], axis=1), kp, vp, bt, sl, sl - 2,
                kernel="xla", window=8)


class TestKernelSelection:
    def test_auto_resolves_xla_off_chip(self):
        kind, reason = pallas_ops.select_paged_kernel(
            "auto", head_dim=64, block_size=16, dtype=jnp.float32)
        assert kind == "xla" and "not tpu" in reason

    def test_forced_pallas_off_chip_runs_interpreter(self):
        c0 = dict(registry.counters("serving"))
        kind, _ = pallas_ops.select_paged_kernel(
            "pallas", head_dim=48, block_size=4, dtype=jnp.float32)
        assert kind == "interpret"
        c1 = registry.counters("serving")
        assert c1["kernel.interpret"] == c0["kernel.interpret"] + 1

    def test_env_knob_and_bad_value(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "xla")
        kind, reason = pallas_ops.select_paged_kernel(
            None, head_dim=64, block_size=16, dtype=jnp.float32)
        assert (kind, reason) == ("xla", "requested")
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "mosaic")
        with pytest.raises(ValueError, match="PADDLE_TPU_PAGED_KERNEL"):
            pallas_ops.select_paged_kernel(
                None, head_dim=64, block_size=16, dtype=jnp.float32)

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="needs >= 2 (forced host) devices")
    def test_mesh_indivisible_heads_demotes_loudly(self):
        # ISSUE 16: a mesh no longer demotes per se — only heads that do
        # not divide the 'mp' axis do, and the demotion names both
        # numbers in a kernel_fallback event
        from paddle_tpu.distributed import spmd

        mesh = spmd.serving_mesh(2)
        c0 = dict(registry.counters("serving"))
        kind, reason = pallas_ops.select_paged_kernel(
            "pallas", head_dim=64, block_size=16, dtype=jnp.float32,
            mesh=mesh, num_heads=3)
        assert kind == "xla"
        assert "3" in reason and "mp=2" in reason
        c1 = registry.counters("serving")
        assert c1["kernel.fallbacks"] == c0["kernel.fallbacks"] + 1
        ev = [e for e in explainer.events(kind="kernel_fallback")
              if e.get("mp") == 2 and e.get("num_heads") == 3]
        assert ev, "head/mp demotion must land a kernel_fallback event"

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="needs >= 2 (forced host) devices")
    def test_mesh_divisible_heads_keeps_per_shard_kernel(self):
        from paddle_tpu.distributed import spmd

        mesh = spmd.serving_mesh(2)
        c0 = dict(registry.counters("serving"))
        kind, reason = pallas_ops.select_paged_kernel(
            "pallas", head_dim=64, block_size=16, dtype=jnp.float32,
            mesh=mesh, num_heads=4)
        assert kind == "interpret"  # cpu: kernel body via interpreter
        assert "per-shard" in reason and "local heads 2" in reason
        c1 = registry.counters("serving")
        assert c1["kernel.fallbacks"] == c0["kernel.fallbacks"]

    def test_tileability_reasons(self):
        # what Mosaic accepts, as compiled for the v5e
        # (tests/test_tpu_lowering.py): any head_dim / block_size, ...
        for dh, bs in ((128, 16), (48, 16), (128, 12), (80, 4)):
            ok, _ = pallas_ops.paged_tileable(dh, bs, jnp.bfloat16, 16)
            assert ok
        # ... no dtype the body has no arithmetic for, ...
        ok, why = pallas_ops.paged_tileable(128, 16, jnp.int8)
        assert not ok and "dtype" in why
        # ... and no KV block beyond the VMEM the pipeline has
        ok, _ = pallas_ops.paged_tileable(256, 32, jnp.float32, 64)
        assert ok  # 2 MiB
        ok, why = pallas_ops.paged_tileable(256, 64, jnp.float32, 64)
        assert not ok and "VMEM" in why  # 4 MiB
        # heads unknown: the block cannot be sized, the dtype still can
        ok, _ = pallas_ops.paged_tileable(256, 64, jnp.float32)
        assert ok


def _prefill_both(q, kp, vp, bt, sl, qo, **plan):
    """`flash_prefill` through the interpreter against the gather route
    and the float64 oracle on every row of the prompt (rows past
    ``seq_len`` are the bucket's padding: nobody reads them). Returns the
    kernel's output."""
    sl_a, qo_a = jnp.asarray(sl, jnp.int32), jnp.asarray(qo, jnp.int32)
    if plan:
        out = pallas_ops._flash_prefill_fused(
            q, kp, vp, bt, sl_a, qo_a, q.shape[-1] ** -0.5, True, **plan)
    else:
        out = pallas_ops.flash_prefill(q, kp, vp, bt, sl_a, qo_a,
                                       kernel="interpret")
    ref = pallas_ops.flash_prefill(q, kp, vp, bt, sl_a, qo_a, kernel="xla")
    want = _oracle64(q, kp, vp, bt, sl, qo)
    atol, rtol = pallas_ops.PAGED_PARITY_TOL[jnp.dtype(q.dtype).name]
    assert bool(jnp.isfinite(out).all())
    for b in range(q.shape[0]):
        n = max(0, min(sl[b] - qo[b], q.shape[1]))
        got = np.asarray(out[b, :n], np.float64)
        np.testing.assert_allclose(got, np.asarray(ref[b, :n], np.float64),
                                   atol=atol, rtol=rtol)
        np.testing.assert_allclose(got, want[b, :n], atol=atol, rtol=rtol)
    return out


class TestFlashPrefillParity:
    """PR 36: `flash_prefill`, the prompt span's read. Program (b, i) is a
    block of query rows of slot b; the slot's live blocks are copied once
    into VMEM as they lie, spans at absolute key positions fold in rising
    order (without the mask where every row sees the whole span), dead
    spans and query blocks past the prompt are skipped. A plan of 16 query
    rows and 32 keys (two blocks of 16) puts offsets and lengths on every
    side of a block's, a span's and a query block's edge."""

    SMALL = dict(block_q=16, span=32)
    CASES = {
        # name: (T, cache_offset, seq_len)
        "cold_full_bucket": (64, 0, 64),
        "cold_shorter_than_bucket": (64, 0, 37),  # dead query blocks
        "cold_one_token": (32, 0, 1),
        "prefix_hit": (32, 48, 80),               # keys 0..47 are cached
        "prefix_hit_short": (32, 64, 75),
        "chunk_ends_mid_block": (32, 32, 55),     # rows 32..54, 55 % 16 = 7
        "chunk_off_a_span_edge": (16, 40, 56),    # offset mid-span
        "last_span_to_the_table_edge": (64, 64, 128),
    }

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_against_both_oracles(self, case, dtype):
        T, off, sl = self.CASES[case]
        q, kp, vp, bt = _case(1, T, 4, 32, 12, 16, 8, dtype=dtype,
                              form="merged", seed=T + off + sl)
        out = _prefill_both(q, kp, vp, bt, [sl], [off], **self.SMALL)
        # a query block wholly past the prompt is skipped: zeros
        dead = -(-(sl - off) // 16) * 16
        assert not np.asarray(out[0, dead:], np.float32).any()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("H,Dh,lanes", [
        (2, 64, 128), (4, 64, 128), (8, 16, 128), (2, 128, 128),
        (3, 64, 192), (2, 24, 48)])
    def test_head_geometries_under_the_default_plan(self, H, Dh, lanes,
                                                    dtype):
        # two heads a lane tile, four tiles' worth, eight heads a tile, a
        # head that owns its tile, and (the interpreter only) widths that
        # split into no whole tiles; two slots, one behind a prefix
        assert pallas_ops._prefill_group_lanes(H, Dh) == lanes
        q, kp, vp, bt = _case(2, 32, H, Dh, 20, 16, 6, dtype=dtype,
                              form="merged", seed=H * Dh)
        _prefill_both(q, kp, vp, bt, [29, 90], [0, 64])

    def test_rows_fold_the_same_keys_in_the_same_order_however_cut(self):
        # spans sit at absolute positions, so a row's output is the same
        # bits in a one-shot prefill, as the tail of a prefix hit and as
        # one chunk of three
        q, kp, vp, bt = _case(1, 96, 4, 32, 12, 16, 8, form="merged",
                              seed=5)
        one = _prefill_both(q, kp, vp, bt, [96], [0], **self.SMALL)
        hit = _prefill_both(q[:, 32:], kp, vp, bt, [96], [32], block_q=16,
                            span=32)
        np.testing.assert_array_equal(np.asarray(one[:, 32:]),
                                      np.asarray(hit))
        for start in (0, 32, 64):
            chunk = _prefill_both(q[:, start:start + 32], kp, vp, bt,
                                  [start + 32], [start], block_q=32, span=32)
            np.testing.assert_array_equal(
                np.asarray(one[:, start:start + 32]), np.asarray(chunk))

    def test_padding_and_dead_keys_cannot_leak(self):
        # keys past seq_len (a stale tenant's rows in the slot's last live
        # block, whole dead blocks behind it) never reach a live row
        q, kp, vp, bt = _case(1, 64, 4, 32, 12, 16, 8, form="merged", seed=8)
        sl, off = [41], [0]
        base = _prefill_both(q, kp, vp, bt, sl, off, **self.SMALL)
        kp2, vp2 = kp, vp
        for pos in range(41, 128):
            blk = int(bt[0, pos // 16])
            kp2 = kp2.at[blk, pos % 16].add(50.0)
            vp2 = vp2.at[blk, pos % 16].add(-50.0)
        got = _prefill_both(q, kp2, vp2, bt, sl, off, **self.SMALL)
        np.testing.assert_array_equal(np.asarray(base[:, :41]),
                                      np.asarray(got[:, :41]))

    def test_which_spans_are_prompt_spans(self):
        # decode's row and a verify span stay with the paged kernel
        assert [t for t in (1, 5, 8, 15, 16, 17, 24, 32, 256, 2048)
                if pallas_ops.prefill_span(t)] == [16, 32, 256, 2048]
        # a bucket takes the largest query block that divides it; the span
        # comes from the pool's geometry alone
        assert pallas_ops._prefill_plan(2048, 16, 128) == (128, 32)
        assert pallas_ops._prefill_plan(48, 16, 128) == (16, 32)
        assert pallas_ops._prefill_plan(1024, 16, 6) == (128, 6)

    def test_selection_follows_the_paged_kernel_and_refuses_loudly(self):
        geo = dict(head_dim=64, block_size=16, dtype=jnp.bfloat16,
                   num_heads=32, table_cols=128)
        c0 = dict(registry.counters("serving"))
        assert pallas_ops.select_prefill_kernel(
            "xla", spans=(256, 2048), **geo)[0] == "xla"
        assert pallas_ops.select_prefill_kernel(
            "interpret", spans=(8, 256, 2048), **geo)[0] == "interpret"
        assert pallas_ops.select_prefill_kernel(
            "pallas", spans=(256, 512, 1024), **geo)[0] == "pallas"
        assert registry.counters("serving")["kernel.fallbacks"] \
            == c0["kernel.fallbacks"]
        # a bucket that is not whole tiles of rows; a merged row that is
        # not whole lane tiles; a slot too long for the kernel's VMEM
        for kind, spans, over, word in (
                ("interpret", (16, 100), {}, "100"),
                ("pallas", (256,), dict(num_heads=12, head_dim=80),
                 "128-lane"),
                ("pallas", (256,), dict(table_cols=1024), "VMEM")):
            got, why = pallas_ops.select_prefill_kernel(
                kind, spans=spans, **{**geo, **over})
            assert got == "xla" and word in why, (got, why)
        assert registry.counters("serving")["kernel.fallbacks"] \
            == c0["kernel.fallbacks"] + 3
        ev = explainer.events(kind="kernel_fallback")[-1]
        assert ev["op"] == "flash_prefill" and "VMEM" in ev["why"]


def _run_one(eng, prompt, n, step=None, **kw):
    out = [eng.prefill(0, prompt, **kw)]
    if step is None:
        for _ in range(n - 1):
            out.append(int(eng.decode_step()[0]))
    else:
        while len(out) < n:
            out.extend(step()[0])
    eng.release(0)
    return out[:n]


class TestEngineTokenParity:
    """Greedy serving tokens must be IDENTICAL across kernel choices on
    the test model (the acceptance contract); sampled tokens too — the
    seeded Gumbel-max argmax margin dwarfs the accumulation-order
    delta at these scales."""

    @pytest.fixture(scope="class")
    def engines(self):
        from paddle_tpu.serving import GenerationEngine

        ekw = dict(max_batch_size=2, buckets=(8, 16), rng_seed=9,
                   block_size=4)
        return (GenerationEngine(_build_model(71), paged_kernel="xla",
                                 **ekw),
                GenerationEngine(_build_model(71), paged_kernel="pallas",
                                 **ekw))

    def test_greedy_and_sampled_tokens_identical(self, engines):
        e_xla, e_pal = engines
        assert e_xla.paged_kernel == "xla"
        assert e_pal.paged_kernel == "interpret"  # cpu: kernel body
        rng = np.random.default_rng(5)
        for i, (pl_, kw) in enumerate([
                (6, dict(temperature=0.0)),
                (9, dict(temperature=0.9, top_k=25)),
                (13, dict(temperature=0.0))]):  # second bucket
            prompt = list(rng.integers(1, VOCAB, pl_))
            want = _run_one(e_xla, prompt, 10, seed=i, **kw)
            got = _run_one(e_pal, prompt, 10, seed=i, **kw)
            assert got == want

    def test_prefix_hit_tokens_identical_across_kernels(self, engines):
        # the fused read path composes with radix prefix sharing: a
        # prefix-hit admission decodes the same tokens either way
        e_xla, e_pal = engines
        rng = np.random.default_rng(7)
        shared = list(rng.integers(1, VOCAB, 8))
        outs = []
        for eng in (e_xla, e_pal):
            _run_one(eng, shared + [3, 4], 6, seed=40)   # publish prefix
            outs.append(_run_one(eng, shared + [5, 6], 6, seed=41))
        assert outs[0] == outs[1]

    def test_one_shot_chunked_and_prefix_hit_prefill_through_the_kernel(
            self):
        # PR 36: the prompt span reads through `flash_prefill` (buckets of
        # whole 16-row tiles) — a one-shot prefill at the 64 bucket, the
        # same prompt in chunks of 16 and its tail behind a cached prefix
        # of 24 give the gather path's greedy tokens, every prefill call
        # counted in `serving.prefill_flash_calls`
        from paddle_tpu.serving import GenerationEngine

        ekw = dict(max_batch_size=2, buckets=(16, 32, 64), rng_seed=9,
                   block_size=4)
        e_xla = GenerationEngine(_build_model(78), paged_kernel="xla",
                                 **ekw)
        e_pal = GenerationEngine(_build_model(78), paged_kernel="pallas",
                                 **ekw)
        st = e_pal.stats()
        assert st["prefill_kernel"] == "interpret", st
        assert registry.gauge("serving.prefill_kernel") == "interpret"
        assert e_xla.stats()["prefill_kernel"] == "xla"
        rng = np.random.default_rng(12)
        prompt = list(rng.integers(1, VOCAB, 40))
        want = _run_one(e_xla, prompt, 8, seed=3)
        c0 = dict(registry.counters("serving"))
        e_pal.begin_prefill(0, prompt, seed=3, chunk_tokens=16)
        tok = None
        while tok is None:
            tok = e_pal.prefill_chunk(0)  # 16 + 16 + 8 rows
        chunked = [tok] + [int(e_pal.decode_step()[0]) for _ in range(7)]
        e_pal.release(0)
        e_pal.reset()  # drop the prefix the chunked run published
        one_shot = _run_one(e_pal, prompt, 8, seed=3)
        _run_one(e_pal, prompt[:24] + [3, 4], 2, seed=4)  # same prefix
        h0 = registry.counters("serving")["prefix_hits"]
        hit = _run_one(e_pal, prompt, 8, seed=3)
        c1 = registry.counters("serving")
        assert c1["prefix_hits"] > h0
        assert chunked == want and one_shot == want and hit == want
        calls = c1["prefill_n"] - c0["prefill_n"]
        assert calls == 3 + 1 + 1 + 1
        assert c1["prefill_flash_calls"] - c0["prefill_flash_calls"] == calls
        assert c1["kernel.fallbacks"] == c0["kernel.fallbacks"]
        e_pal.pool.audit()

    def test_spec_verify_span_tokens_identical(self):
        from paddle_tpu.serving import (DraftVerifyEngine,
                                        GenerationEngine)

        ekw = dict(max_batch_size=1, buckets=(8, 16), rng_seed=9,
                   block_size=4)
        plain = GenerationEngine(_build_model(73), paged_kernel="xla",
                                 **ekw)
        spec = DraftVerifyEngine(_build_model(73), _build_model(74),
                                 draft_k=3, paged_kernel="pallas", **ekw)
        assert spec.paged_kernel == "interpret"
        rng = np.random.default_rng(3)
        prompt = list(rng.integers(1, VOCAB, 7))
        want = _run_one(plain, prompt, 9, seed=0)
        got = _run_one(spec, prompt, 9, step=spec.decode_step_spec,
                       seed=0)
        assert got == want
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_zero_post_warmup_compiles_under_kernel_layer(self):
        # the replay fingerprint must be stable under kernel selection:
        # with the fused kernel active, a steady decode window adds ZERO
        # decode compiles, zero fast-path demotions and zero rebuilds
        # (PR 8 contract intact — kernel choice is resolved at build,
        # so no executable churn is even possible)
        from paddle_tpu.serving import GenerationEngine

        eng = GenerationEngine(_build_model(75), max_batch_size=2,
                               buckets=(8,), rng_seed=9, block_size=4,
                               paged_kernel="pallas")
        eng.prefill(0, [5, 9, 2, 7], seed=0)
        eng.prefill(1, [8, 1, 3], seed=1)
        for _ in range(3):
            eng.decode_step()  # warmup: radar has seen the signature
        c0 = dict(registry.counters("serving"))
        f0 = dict(registry.counters("fastpath"))
        for _ in range(2 * eng._audit_every):
            eng.decode_step()
        c1 = registry.counters("serving")
        f1 = registry.counters("fastpath")
        assert c1["decode_compiles"] == c0["decode_compiles"]
        assert f1["decode_demotions"] == f0["decode_demotions"]
        assert f1["decode_rebuilds"] == f0["decode_rebuilds"]
        assert f1["decode_audit_runs"] > f0["decode_audit_runs"]
        eng.reset()
        eng.pool.audit()


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 (forced host) devices for mp=2")
class TestMeshShardedKernel:
    """ISSUE 16 tentpole: the fused kernel route survives an mp mesh.
    Per-shard execution through shard_map must be token-BITWISE with the
    single-chip fused engine (each head's online softmax is computed
    whole on exactly one shard — nothing crosses the 'mp' axis), with
    zero post-warmup compiles/demotions, for plain decode, spec decode,
    and across a target+drafter weight hot-swap."""

    EKW = dict(max_batch_size=2, buckets=(8, 16), rng_seed=9,
               block_size=4)

    @staticmethod
    def _lint_mod():
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "sharding_lint.py")
        spec = importlib.util.spec_from_file_location("sharding_lint",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_serving_mesh_validates_head_divisibility(self):
        from paddle_tpu.distributed import spmd

        with pytest.raises(ValueError, match=r"mp=3.*n_head=2"):
            spmd.serving_mesh(3, model=_build_model(77))

    def test_mp2_fused_decode_bitwise_zero_recompiles(self):
        from paddle_tpu.distributed import spmd
        from paddle_tpu.serving import GenerationEngine

        single = GenerationEngine(_build_model(76),
                                  paged_kernel="pallas", **self.EKW)
        mesh = spmd.serving_mesh(2, model=_build_model(76))
        sharded = GenerationEngine(_build_model(76),
                                   paged_kernel="pallas", mesh=mesh,
                                   **self.EKW)
        assert sharded.paged_kernel == "interpret"  # cpu: kernel body
        assert sharded.stats()["paged_kernel_sharded"]
        rng = np.random.default_rng(8)
        for i, kw in enumerate([dict(temperature=0.0),
                                dict(temperature=0.9, top_k=25)]):
            prompt = list(rng.integers(1, VOCAB, 6 + 3 * i))
            want = _run_one(single, prompt, 9, seed=i, **kw)
            got = _run_one(sharded, prompt, 9, seed=i, **kw)
            assert got == want
        # KV pools are head-sharded — the lint agrees nothing was left
        # replicated (the demotion this PR removed)
        desc = sharded.describe_sharding()
        assert desc["paged_kernel_sharded"]
        assert all(pool["spec"] == [None, None, "mp"]
                   for pool in desc["kv_pools"])
        assert all(pool["heads"] == 2 and len(pool["shape"]) == 3
                   for pool in desc["kv_pools"])
        lint = self._lint_mod().lint_engine
        assert lint(desc, min_bytes=0) == []
        # ... and the same pools left replicated are named by the lint
        # (the merged shape carries no head axis: the record's "heads"
        # is what the lint divides by mp)
        flat = {**desc, "kv_pools": [{**pool, "spec": []}
                                     for pool in desc["kv_pools"]]}
        found = lint(flat, min_bytes=0)
        assert len(found) == len(desc["kv_pools"])
        assert "2 heads divide mp" in found[0]
        # zero post-warmup churn, same window as the single-chip gate
        sharded.prefill(0, [5, 9, 2, 7], seed=0)
        for _ in range(3):
            sharded.decode_step()
        c0 = dict(registry.counters("serving"))
        f0 = dict(registry.counters("fastpath"))
        for _ in range(2 * sharded._audit_every):
            sharded.decode_step()
        c1 = registry.counters("serving")
        f1 = registry.counters("fastpath")
        assert c1["decode_compiles"] == c0["decode_compiles"]
        assert c1["kernel.fallbacks"] == c0["kernel.fallbacks"]
        assert f1["decode_demotions"] == f0["decode_demotions"]
        assert f1["decode_rebuilds"] == f0["decode_rebuilds"]
        sharded.reset()
        sharded.pool.audit()

    def test_mp2_spec_decode_bitwise(self):
        from paddle_tpu.distributed import spmd
        from paddle_tpu.serving import (DraftVerifyEngine,
                                        GenerationEngine)

        plain = GenerationEngine(_build_model(73), paged_kernel="xla",
                                 **self.EKW)
        mesh = spmd.serving_mesh(2, model=_build_model(73))
        spec = DraftVerifyEngine(_build_model(73), _build_model(74),
                                 draft_k=3, paged_kernel="pallas",
                                 mesh=mesh, **self.EKW)
        st = spec.stats()
        assert st["paged_kernel_sharded"] and st["draft_kernel_sharded"]
        rng = np.random.default_rng(3)
        for i, kw in enumerate([dict(temperature=0.0),
                                dict(temperature=0.8, top_k=20)]):
            prompt = list(rng.integers(1, VOCAB, 7 + 2 * i))
            want = _run_one(plain, prompt, 9, seed=i, **kw)
            got = _run_one(spec, prompt, 9,
                           step=spec.decode_step_spec, seed=i, **kw)
            assert got == want
        # drafter pools ride the same head-sharded layout
        draft_pools = [p for p in spec.describe_sharding()["kv_pools"]
                       if p.get("draft")]
        assert draft_pools and all(p["spec"] == [None, None, "mp"]
                                   for p in draft_pools)
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_draft_swap_rebuilds_kv_and_recovers_acceptance(self):
        from paddle_tpu.distributed import spmd
        from paddle_tpu.serving import (DraftVerifyEngine,
                                        GenerationEngine)

        ekw = dict(self.EKW, max_batch_size=1)
        plain = GenerationEngine(_build_model(73), paged_kernel="xla",
                                 **ekw)
        mesh = spmd.serving_mesh(2, model=_build_model(73))
        spec = DraftVerifyEngine(_build_model(73), _build_model(74),
                                 draft_k=3, paged_kernel="pallas",
                                 mesh=mesh, **ekw)
        rng = np.random.default_rng(5)
        prompt = list(rng.integers(1, VOCAB, 7))
        wp = [plain.prefill(0, prompt, seed=0)]
        ws = [spec.prefill(0, prompt, seed=0)]
        while len(wp) < 6:
            wp.append(int(plain.decode_step()[0]))
        while len(ws) < 6:
            ws.extend(spec.decode_step_spec()[0])
        # mid-stream hot-swap: same target weights, drafter becomes a
        # TWIN of the target — spec_decode's exact-acceptance bound
        t_state = dict(_build_model(73).gpt.state_dict())
        d_state = dict(_build_model(73).gpt.state_dict())
        c0 = dict(registry.counters("serving"))
        spec.swap_weights(dict(t_state), draft_state=d_state)
        plain.swap_weights(t_state)
        assert registry.counters("serving")["draft_swaps"] \
            == c0["draft_swaps"] + 1
        while len(wp) < 14:
            wp.append(int(plain.decode_step()[0]))
        while len(ws) < 14:
            ws.extend(spec.decode_step_spec()[0])
        # the rebuilt drafter KV continues BITWISE mid-request...
        assert ws[:14] == wp[:14]
        # ...and the twin drafter's rounds are fully accepted in the
        # new weight generation (per-generation acceptance isolates the
        # pre-swap wrong-drafter rounds)
        by_gen = spec.acceptance_by_generation()
        gen = spec.prefix_cache.generation
        assert by_gen[gen] == 1.0
        assert by_gen[gen - 1] < 1.0
        spec.release(0)
        plain.release(0)
        spec.pool.audit()
        spec.draft_pool.audit()


class TestKernelMismatchFault:
    def test_fault_trips_parity_gate(self):
        q, kp, vp, bt = _case(2, 1, 2, 16, 8, 4, 2, seed=3)
        sl = jnp.asarray([5, 7], jnp.int32)
        qo = jnp.asarray([4, 6], jnp.int32)
        ref = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                         kernel="xla")
        faults.configure("kernel_mismatch")
        try:
            bad = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                             kernel="interpret")
        finally:
            faults.reset()
        atol, rtol = pallas_ops.PAGED_PARITY_TOL["float32"]
        assert not np.allclose(np.asarray(bad), np.asarray(ref),
                               atol=atol, rtol=rtol)
        # disarmed: a fresh fused call is clean again
        good = pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                          kernel="interpret")
        np.testing.assert_allclose(np.asarray(good), np.asarray(ref),
                                   atol=atol, rtol=rtol)


class TestLatentKernelParity:
    """`mla_paged_attention`: the kernel body through the interpreter
    against the XLA gather route, over a latent pool [Nb, bs, W]. Tolerance:
    PAGED_PARITY_TOL, the paged family's own (f32 differs by reduction
    order only; bf16 keeps probabilities in f32 where the XLA route rounds
    them)."""

    @staticmethod
    def _case(B, H, W, Nb, bs, M, dtype, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((B, H, W)), dtype)
        pool = jnp.asarray(rng.standard_normal((Nb, bs, W)), dtype)
        ids = rng.permutation(np.arange(1, Nb))[:B * M].reshape(B, M)
        return q, pool, jnp.asarray(ids, jnp.int32)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_ragged_lengths_and_a_zero_length_lane(self, dtype):
        # bs=4, 8 blocks a program's worth of keys = 32: lengths just
        # under / on / over a block and a program boundary, a released lane
        # (zeroed table row, length 1: reads garbage block 0) and a lane of
        # length 0 (reads nothing: zeros from both routes)
        q, pool, bt = self._case(6, 4, 128, 80, 4, 12, dtype)
        bt = bt.at[4].set(0)
        sl = jnp.asarray([3, 4, 33, 47, 1, 0], jnp.int32)
        fused = pallas_ops.mla_paged_attention(q, pool, bt, sl, 0.17,
                                               kernel="interpret")
        ref = pallas_ops.mla_paged_attention(q, pool, bt, sl, 0.17,
                                             kernel="xla")
        atol, rtol = pallas_ops.PAGED_PARITY_TOL[jnp.dtype(dtype).name]
        np.testing.assert_allclose(np.asarray(fused, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=atol, rtol=rtol)
        assert bool(jnp.isfinite(fused).all())
        assert not np.asarray(fused[5], np.float32).any()
        # dead lanes do not perturb the live ones
        solo = pallas_ops.mla_paged_attention(q[:4], pool, bt[:4], sl[:4],
                                              0.17, kernel="interpret")
        np.testing.assert_array_equal(np.asarray(fused[:4], np.float32),
                                      np.asarray(solo, np.float32))

    def test_selection_and_refusals(self):
        kind, why = pallas_ops.select_mla_paged_kernel(
            "pallas", row_width=640, block_size=16, dtype=jnp.bfloat16)
        assert kind == "interpret" and "interpreter" in why
        assert pallas_ops.select_mla_paged_kernel(
            None, row_width=640, block_size=16,
            dtype=jnp.bfloat16)[0] == "xla"
        with pytest.raises(ValueError, match="unknown paged-attention"):
            pallas_ops.mla_paged_attention(None, None, None, None, 1.0,
                                           kernel="interpet")
