"""Do the Pallas kernels lower and compile for the TPU under the package's
own defaults (x64 on, matmul precision "highest")? — the half of "does it
compile" that needs no chip.

Two strengths, both CPU-only:

* cross-lowering: ``jit(f).trace(avals).lower(lowering_platforms=("tpu",))``
  from the CPU backend. With no TPU backend to ask, Pallas assumes the
  OLDEST libtpu it supports, so this is the strictest reading of the kernel
  bodies: one python-float constant (an f64 under x64) fails it.
* ahead-of-time compilation: the installed libtpu describes a v5e 2x2 host
  without owning one (``jax.experimental.topologies``), and XLA + Mosaic
  compile against it — the same compiler the chip runs. Run in a child
  process so that libtpu never loads into the test process.

What neither can say is that the numbers are right on the chip; that is
``chip_smoke.py``'s kernels leg.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu  # noqa: F401  (installs the package defaults under test)
from paddle_tpu.core import lazy
from paddle_tpu.distributed import spmd
from paddle_tpu.ops import pallas_ops as po

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLASH_SHAPE = (8, 1024, 16, 64)  # gpt2-medium's attention at bs8 seq1024


def _lowered_text(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()


def _paged_avals(T, head_dim, dtype, sharding=None, repl=None):
    B, H, bs, M, nb = 8, 16, 16, 64, 513
    sds = jax.ShapeDtypeStruct
    return (sds((B, T, H, head_dim), dtype, sharding=sharding),
            sds((nb, bs, H, head_dim), dtype, sharding=sharding),
            sds((nb, bs, H, head_dim), dtype, sharding=sharding),
            sds((B, M), jnp.int32, sharding=repl),
            sds((B,), jnp.int32, sharding=repl),
            sds((B,), jnp.int32, sharding=repl))


def test_package_defaults_are_what_is_under_test():
    assert jax.config.jax_enable_x64
    assert jax.config.jax_default_matmul_precision == "highest"


class TestCrossLowering:
    def test_flash_forward(self):
        a = jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16)
        text = _lowered_text(
            lambda q, k, v: po._flash_attention_tpu(q, k, v, causal=True),
            a, a, a)
        assert text.count("tpu_custom_call") == 1

    def test_flash_grad(self):
        a = jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16)

        def loss(q, k, v):
            return po._flash_attention_tpu(q, k, v, causal=True).astype(
                jnp.float32).sum()

        text = _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), a, a, a)
        assert text.count("tpu_custom_call") == 3  # fwd, dq, dkv

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("head_dim", [64, 128])
    @pytest.mark.parametrize("T", [1, 5])  # decode, spec verify (K+1)
    def test_paged(self, T, head_dim, dtype):
        text = _lowered_text(
            lambda *a: po.paged_attention(*a, kernel="pallas"),
            *_paged_avals(T, head_dim, dtype))
        assert "tpu_custom_call" in text

    @pytest.mark.parametrize("T", [1, 5])
    def test_paged_per_shard_on_the_virtual_mesh(self, T):
        mesh = spmd.serving_mesh(2)
        head = NamedSharding(mesh, P(None, None, "mp", None))
        text = _lowered_text(
            lambda *a: po.paged_attention(*a, kernel="pallas", mesh=mesh),
            *_paged_avals(T, 64, jnp.bfloat16, head,
                          NamedSharding(mesh, P())))
        assert "tpu_custom_call" in text

    def test_flash_per_shard_on_the_virtual_mesh(self, monkeypatch):
        # the SPMD train step's route: flash_attention itself plans the
        # split from the installed mesh (GSPMD cannot partition a Mosaic
        # custom call and refuses to try)
        monkeypatch.setattr(po, "_on_tpu", lambda: True)
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        sh = NamedSharding(mesh, P("dp", None, "mp", None))
        a = jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16, sharding=sh)
        c0 = dict(po._flash_counters)
        with spmd.spmd_guard(mesh):
            text = _lowered_text(
                lambda q, k, v: po.flash_attention(q, k, v, causal=True),
                a, a, a)
        assert "tpu_custom_call" in text
        assert po._flash_counters["flash.pallas"] == c0["flash.pallas"] + 1
        assert po._flash_counters["flash.fallbacks"] == c0["flash.fallbacks"]
        # each shard's kernel sees its own slice: batch/dp, heads/mp
        assert "4x1024x8x64" in text.replace(" ", "")


    def test_spec_decode_sampling_steps_gate_their_filters(self):
        """The drafter's scan step and the verify step sample through
        `sampling.sample_tokens` (rows replicated K+1 times): the
        `lax.cond` around each filter and the `fori_loop` of its passes
        trace inside the scan and lower for the TPU, with no sort left."""
        from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                          GPTModel)
        from paddle_tpu.serving import DraftVerifyEngine

        def build(n_layer, d_model):
            return GPTForPretraining(GPTModel(GPTConfig(
                vocab_size=96, n_layer=n_layer, n_head=2, d_model=d_model,
                seq_len=64)))

        eng = DraftVerifyEngine(build(2, 48), build(1, 32), draft_k=3,
                                max_batch_size=2, buckets=(8,), rng_seed=9,
                                block_size=4, paged_kernel="xla")
        tail = tuple(jnp.asarray(a) for a in (
            eng._cur_lens, eng._keys, eng._gen_idx, eng._temps,
            eng._top_ks, eng._top_ps))
        last = jnp.asarray(eng._last_tokens)
        steps = {
            "draft": (eng._draft_round_pure, (
                eng._draft_arrays(), tuple(eng._dk), tuple(eng._dv), last)
                + tail + (jnp.asarray(eng._draft_tables),)),
            "verify": (eng._verify_pure, (
                eng._state_arrays(), tuple(eng._k), tuple(eng._v), last,
                eng._garbage_drafts) + tail + (
                jnp.asarray(eng._active), jnp.asarray(eng._block_tables))),
        }
        for name, (fn, args) in steps.items():
            text = _lowered_text(fn, *args)
            assert "stablehlo.sort" not in text, name
            assert text.count("stablehlo.case") >= 2, name
            assert "stablehlo.while" in text, name


class TestFlashMeshPlan:
    """flash_attention's resolution under a mesh (platform patched in)."""

    def _mesh(self, shape, names):
        n = int(np.prod(shape))
        return jax.sharding.Mesh(
            np.array(jax.devices()[:n]).reshape(shape), names)

    def test_plans(self):
        plan = po._flash_mesh_spec
        assert plan(self._mesh((2, 2), ("dp", "mp")), 8, 16) == (
            P(("dp",), None, "mp", None), None)
        assert plan(self._mesh((2, 2), ("dp", "ep")), 8, 16) == (
            P(("dp", "ep"), None, None, None), None)
        assert plan(self._mesh((1, 1), ("dp", "mp")), 8, 16) == (None, None)
        _, why = plan(self._mesh((2, 2), ("dp", "mp")), 8, 3)
        assert "3 heads" in why and "mp=2" in why
        _, why = plan(self._mesh((2, 2), ("dp", "mp")), 3, 16)
        assert "batch 3" in why
        _, why = plan(self._mesh((2, 2, 2), ("dp", "pp", "mp")), 8, 16)
        assert "pp" in why

    def test_unplanned_mesh_takes_xla_loudly(self, monkeypatch):
        from paddle_tpu.profiler import explainer

        monkeypatch.setattr(po, "_on_tpu", lambda: True)
        mesh = self._mesh((2, 2), ("dp", "mp"))
        q = jnp.zeros((2, 128, 3, 64), jnp.bfloat16)  # 3 heads over mp=2
        c0 = dict(po._flash_counters)
        with spmd.spmd_guard(mesh):
            out = po.flash_attention(q, q, q, causal=True)
        assert out.shape == q.shape
        assert po._flash_counters["flash.xla"] == c0["flash.xla"] + 1
        assert po._flash_counters["flash.fallbacks"] \
            == c0["flash.fallbacks"] + 1
        ev = explainer.events(kind="kernel_fallback")[-1]
        assert "3 heads" in ev["why"]
        lazy.drop_plans("test boundary")


_AOT_CHILD = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
except Exception as e:
    print(json.dumps({"unavailable": f"{type(e).__name__}: {e}"[:300]}))
    sys.exit(0)
import paddle_tpu
from paddle_tpu.core import lazy
from paddle_tpu.ops import pallas_ops as po
assert jax.config.jax_enable_x64
po._on_tpu = lambda: True  # the platform these programs are compiled for
devs = topo.devices
one = NamedSharding(Mesh(np.array(devs[:1]), ("x",)), P())
sds = jax.ShapeDtypeStruct
out = {"device_kind": devs[0].device_kind}

def compile_(name, fn, *avals):
    try:
        text = jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        out[name] = {"custom_calls": text.count('"tpu_custom_call"'),
                     "collectives": sum(text.count(c + "(") for c in (
                         "all-gather", "all-reduce", "all-to-all",
                         "collective-permute"))}
    except Exception as e:
        out[name] = f"{type(e).__name__}: {e}"[:600]

def loss(q, k, v):
    return po.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

a = sds((8, 1024, 16, 64), jnp.bfloat16, sharding=one)
compile_("flash_grad", jax.grad(loss, argnums=(0, 1, 2)), a, a, a)

def paged(T, dh, dt, head, repl, mesh=None, H=16, bs=16, pool=None, B=8,
          M=64, nb=513):
    # pools 4-D as the public op still takes them (merged on entry), or,
    # with `pool` (their sharding), in the engine's form [nb, bs, H*dh]
    shape = (nb, bs, H, dh) if pool is None else (nb, bs, H * dh)
    return (lambda *x: po.paged_attention(*x, kernel="pallas", mesh=mesh),
            sds((B, T, H, dh), dt, sharding=head),
            sds(shape, dt, sharding=pool or head),
            sds(shape, dt, sharding=pool or head),
            sds((B, M), jnp.int32, sharding=repl),
            sds((B,), jnp.int32, sharding=repl),
            sds((B,), jnp.int32, sharding=repl))

for T in (1, 5):
    for dh in (64, 128):
        for dt in (jnp.bfloat16, jnp.float32):
            compile_(f"paged_T{T}_dh{dh}_{jnp.dtype(dt).name}",
                     *paged(T, dh, dt, one, one))
# geometry: no head_dim / block_size fails to tile where the merged row is
# whole 128-lane tiles (16 x 80 = 1280; 12 x 80 = 960 is paged_tileable's to
# refuse: the kernel's own block copies need whole tiles) ...
compile_("paged_odd", *paged(3, 80, jnp.float32, one, one, H=16, bs=8))
# ... but both halves of K and of V must fit VMEM: 2 MiB blocks do (one a
# program), 4 MiB do not (paged_tileable's _PAGED_VMEM_BUDGET)
compile_("paged_block_2MiB", *paged(1, 256, jnp.float32, one, one, H=64,
                                    bs=32))
compile_("paged_block_4MiB", *paged(1, 256, jnp.float32, one, one, H=64,
                                    bs=64))
mp = Mesh(np.array(devs), ("mp",))
compile_("paged_mp4", *paged(1, 64, jnp.bfloat16,
                             NamedSharding(mp, P(None, None, "mp", None)),
                             NamedSharding(mp, P()), mesh=mp,
                             pool=NamedSharding(mp, P(None, None, "mp"))))
compile_("paged_merged_T1_dh64", *paged(1, 64, jnp.bfloat16, one, one,
                                        pool=one))
# the two gpt serving cells' own calls (gpt3-1.3b: 32 heads x 64, block 16,
# bf16, 128 table columns; 32 slots over 3,073 blocks, 8 over 1,025) and the
# keys a program of each folds
for cell, (slots, blocks) in {"chat": (32, 3073),
                              "long_prefill": (8, 1025)}.items():
    compile_(f"paged_{cell}_cell", *paged(1, 64, jnp.bfloat16, one, one,
                                          H=32, pool=one, B=slots, M=128,
                                          nb=blocks))
    out[f"paged_{cell}_cell"]["keys_per_program"] = \
        po.paged_keys_per_program(16, 32, 64, jnp.bfloat16, 128)
# a kernel's Mosaic body, debug info (paths, lines) set aside
import base64, hashlib, re
from jax._src.interpreters import mlir
from jax._src.lib.mlir import ir

def body_sha256(fn, *avals):
    body = re.search(r"body.22: .22([A-Za-z0-9+/=]+)",
                     jax.jit(fn).trace(*avals).lower(
                         lowering_platforms=("tpu",)).as_text()).group(1)
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()

try:
    out["paged_chat_cell"]["body_sha256"] = body_sha256(*paged(
        1, 64, jnp.bfloat16, one, one, H=32, pool=one, B=32, M=128, nb=3073))
except Exception as e:
    out["paged_chat_cell_body"] = f"{type(e).__name__}: {e}"[:600]
# the training cell's forward kernel (gpt2-medium: 8 x 1024 x 16 heads x 64,
# causal): PR 36 added a second flash forward, for serving's prompt span,
# and left this one as it was
try:
    out["flash_fwd_body_sha256"] = body_sha256(
        lambda q, k, v: po._flash_attention_tpu(q, k, v, causal=True),
        a, a, a)
except Exception as e:
    out["flash_fwd_body"] = f"{type(e).__name__}: {e}"[:600]
# ... and that one, `flash_prefill`, at the long-prefill cell's geometry: one
# slot's bucket of 2048 rows over 32 x 64 heads, block 16, 128 table columns
prefill_2048 = (
    lambda *x: po.flash_prefill(*x, kernel="pallas"),
    sds((1, 2048, 32, 64), jnp.bfloat16, sharding=one),
    sds((1025, 16, 2048), jnp.bfloat16, sharding=one),
    sds((1025, 16, 2048), jnp.bfloat16, sharding=one),
    sds((1, 128), jnp.int32, sharding=one),
    sds((1,), jnp.int32, sharding=one), sds((1,), jnp.int32, sharding=one))
compile_("flash_prefill_2048", *prefill_2048)
try:
    out["flash_prefill_2048"]["body_sha256"] = body_sha256(*prefill_2048)
    out["flash_prefill_2048"]["plan"] = list(po._prefill_plan(2048, 16, 128))
except Exception as e:
    out["flash_prefill_2048_body"] = f"{type(e).__name__}: {e}"[:600]
# the xing4 cell's kernels at its sizes: the latent (MLA) decode kernel, 32
# slots x 32 heads over 640-lane rows, 5,633 blocks of 16, 176 table columns;
# and XLA:TPU's own grouped matmul for the dropless expert layer's decode
# rows, with the precision the layer names (bf16 operands under the package's
# global "highest" end in Mosaic's "Bad lhs type")
mla = (lambda *x: po.mla_paged_attention(*x, 0.1, kernel="pallas"),
       sds((32, 32, 640), jnp.bfloat16, sharding=one),
       sds((5633, 16, 640), jnp.bfloat16, sharding=one),
       sds((32, 176), jnp.int32, sharding=one),
       sds((32,), jnp.int32, sharding=one))
compile_("xing4_mla_paged", *mla)
try:
    out["xing4_mla_paged"]["body_sha256"] = body_sha256(*mla)
    out["xing4_mla_paged"]["keys_per_program"] = \
        po.mla_keys_per_program(16, 176)
except Exception as e:
    out["xing4_mla_paged_body"] = f"{type(e).__name__}: {e}"[:600]
compile_("xing4_grouped_matmul",
         lambda x, w, g: jax.lax.ragged_dot(
             x, w, g, precision=po._dot_precision(x.dtype),
             preferred_element_type=jnp.float32),
         sds((128, 3584), jnp.bfloat16, sharding=one),
         sds((64, 3584, 2048), jnp.bfloat16, sharding=one),
         sds((64,), jnp.int32, sharding=one))
# ISSUE 38: the expert layers' grouped matmuls at the three expert cells'
# decode shapes through `grouped_matmul`: one Mosaic call, the bank read as
# it lies (no copy of its shape), the plan's tiles
GROUPED = {"sdar_gate_up": (1024, 2048, 1536, 128),
           "sdar_down": (1024, 768, 2048, 128),
           "commanda_gate_up": (256, 4096, 8192, 16),
           "commanda_down": (256, 4096, 4096, 16),
           "xing4_gate_up": (128, 3584, 2048, 64),
           "xing4_down": (128, 1024, 3584, 64)}
import re
for cell, (M, K, N, G) in GROUPED.items():
    try:
        text = jax.jit(
            lambda x, w, g: po.grouped_matmul(x, w, g, kernel="pallas")
        ).trace(sds((M, K), jnp.bfloat16, sharding=one),
                sds((G, K, N), jnp.bfloat16, sharding=one),
                sds((G,), jnp.int32, sharding=one)).lower(
                    lowering_platforms=("tpu",)).compile().as_text()
        out[f"grouped_{cell}"] = {
            "custom_calls": text.count('"tpu_custom_call"'),
            "kernels": len(re.findall(r"%grouped_matmul(?:\.\d+)? = ", text)),
            "ragged": text.count("ragged-dot"),
            # anything that makes an array of the bank's shape
            "bank_copies": [ln.strip()[:100] for ln in text.splitlines()
                            if f" = bf16[{G},{K},{N}]" in ln
                            and " parameter(" not in ln],
            "plan": list(po._grouped_plan(M, K, N, G, jnp.bfloat16)[1])}
    except Exception as e:
        out[f"grouped_{cell}"] = f"{type(e).__name__}: {e}"[:600]
# the serving cell's layer: the step's new rows written into donated pools,
# then the kernel over them (gpt3-1.3b: 3,073 blocks x 16 x 32 heads x 64,
# 32 slots, 128 table columns). The pools must enter row-major and stay
# where they are: nothing of a pool's size but the pools themselves
# (parameters, bitcasts of them) and the in-place write.
import re
from paddle_tpu.core import autograd as ag
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTAttention, GPTConfig
from paddle_tpu.ops import kv_pool

attn = GPTAttention(GPTConfig(n_layer=1, n_head=32, d_model=2048,
                              seq_len=2048, dtype="bfloat16"))
attn.eval()
NB, BS, SLOTS, COLS = 3073, 16, 32, 128
pool = jax.eval_shape(lambda: kv_pool.zeros(NB, BS, 32, 64, jnp.bfloat16))
POOL = int(np.prod(pool.shape))

def serve_layer(x, kp, vp, bt, off, sl):
    with ag.no_grad(), lazy.lazy_guard(False):
        y, (nk, nv) = attn(Tensor(x), cache=(Tensor(kp), Tensor(vp)),
                           cache_offset=Tensor(off), seq_lens=Tensor(sl),
                           block_tables=Tensor(bt), paged_kernel="pallas")
    return y._data, nk._data, nv._data

HEAD = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = ([a-z0-9]+)\[([0-9,]*)\]"
                  r"\S* ([\w\-]+)\(")

def pool_sized(text):
    # opcode -> count over the instructions that yield one array of a
    # pool's size; a fusion counts as "write" when its body holds the
    # scatter (or dynamic-update-slice) of that size
    writes, body = set(), None
    for line in text.splitlines():
        m = re.match(r"^%?([\w.\-]+) (?:\([^)]*\) -> .* )?\{$", line)
        if m:
            body = m.group(1)
        h = HEAD.match(line)
        if h and h.group(3) in ("scatter", "dynamic-update-slice") \
                and body and int(np.prod([int(d) for d in
                                          h.group(2).split(",")])) == POOL:
            writes.add(body)
    found = {}
    for line in text.splitlines():
        h = HEAD.match(line)
        if not h or not h.group(2):
            continue
        if int(np.prod([int(d) for d in h.group(2).split(",")])) != POOL:
            continue
        op = h.group(3)
        if op == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", line)
            op = "write" if called and called.group(1) in writes \
                else "fusion"
        found[op] = found.get(op, 0) + 1
    return found

for T in (1, 5):
    avals = (sds((SLOTS, T, 2048), jnp.bfloat16, sharding=one),
             sds(pool.shape, pool.dtype, sharding=one),
             sds(pool.shape, pool.dtype, sharding=one),
             sds((SLOTS, COLS), jnp.int32, sharding=one),
             sds((SLOTS,), jnp.int32, sharding=one),
             sds((SLOTS,), jnp.int32, sharding=one))
    try:
        c = jax.jit(serve_layer, donate_argnums=(1, 2)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
        fmt = c.input_formats[0][1]
        out[f"serve_layer_T{T}"] = {
            "pool_bytes": POOL * 2,
            "pool_layout": list(fmt.layout.major_to_minor),
            "pool_sized": pool_sized(c.as_text()),
            "temp_bytes": int(c.memory_analysis().temp_size_in_bytes),
            "alias_bytes": int(c.memory_analysis().alias_size_in_bytes),
            "custom_calls": c.as_text().count('"tpu_custom_call"')}
    except Exception as e:
        out[f"serve_layer_T{T}"] = f"{type(e).__name__}: {e}"[:600]

# the same layer on a PROMPT span (PR 36): one slot's bucket of rows, the
# two gpt cells' buckets over their own pools. Through the kernel the layer
# holds one `flash_prefill` custom call and no float32 array of every
# head's scores [32, L, 2048]; the gather path (no kernel: the parent's
# prefill, and today's "xla" route) holds them, so the search is not blind
def score_arrays(text, L):
    return sorted(set(re.findall(
        rf"f32\[(?:1,)?32,{L},2048\]", text)))

def prefill_layer(kernel):
    def f(x, kp, vp, bt, off, sl):
        with ag.no_grad(), lazy.lazy_guard(False):
            y, (nk, nv) = attn(Tensor(x), cache=(Tensor(kp), Tensor(vp)),
                               cache_offset=Tensor(off), seq_lens=Tensor(sl),
                               block_tables=Tensor(bt), paged_kernel=kernel)
        return y._data, nk._data, nv._data
    return f

for L, blocks, kernel in ((256, NB, "pallas"), (512, NB, "pallas"),
                          (1024, NB, "pallas"), (2048, 1025, "pallas"),
                          (2048, 1025, None)):
    pl_ = sds((blocks, BS, 2048), jnp.bfloat16, sharding=one)
    name = f"prefill_layer_L{L}" + ("" if kernel else "_gather")
    try:
        c = jax.jit(prefill_layer(kernel), donate_argnums=(1, 2)).trace(
            sds((1, L, 2048), jnp.bfloat16, sharding=one), pl_, pl_,
            sds((1, COLS), jnp.int32, sharding=one),
            sds((1,), jnp.int32, sharding=one),
            sds((1,), jnp.int32, sharding=one)).lower(
                lowering_platforms=("tpu",)).compile()
        text = c.as_text()
        out[name] = {
            "custom_calls": text.count('"tpu_custom_call"'),
            "kernels": sorted(set(re.findall(
                r"%(flash_prefill|paged_attention\w*?)(?:\.\d+)? = ", text))),
            "score_arrays": score_arrays(text, L),
            "temp_bytes": int(c.memory_analysis().temp_size_in_bytes),
            "alias_bytes": int(c.memory_analysis().alias_size_in_bytes),
            "pool_bytes": blocks * BS * 2048 * 2}
    except Exception as e:
        out[name] = f"{type(e).__name__}: {e}"[:600]

# the command-a-plus cell's two kinds of attention layer at its own sizes
# (PR 35: 128 query heads over 8 key/value heads of 128, 32 slots; a full
# layer's pools of 1 + 32 x 560 blocks behind 560 table columns, a window
# layer's of 1 + 32 x 257 behind the slot's 257 ring columns), donated: both
# kinds of pool enter row-major and are written in place, one custom call a
# layer under its own name
from paddle_tpu.models.cohere2_moe import (Cohere2MoeAttention,
                                            Cohere2MoeConfig)

cmd_cfg = Cohere2MoeConfig(dtype="bfloat16", num_hidden_layers=1,
                           vocab_size=128, experts_held=(0, 1))
for kind, sliding, cols, blocks in (("window", True, 257, 1 + 32 * 257),
                                    ("full", False, 560, 1 + 32 * 560)):
    cattn = Cohere2MoeAttention(cmd_cfg, sliding)
    cattn.eval()
    pool = sds((blocks, 16, 1024), jnp.bfloat16, sharding=one)
    POOL = blocks * 16 * 1024

    def cmd_layer(u, kp, vp, bt, off, sl, cattn=cattn):
        with ag.no_grad(), lazy.lazy_guard(False):
            y, (nk, nv) = cattn(u, off[:, None], cache=(kp, vp),
                                cache_offset=off, seq_lens=sl,
                                block_tables=bt, paged_kernel="pallas")
        return y, nk, nv

    try:
        c = jax.jit(cmd_layer, donate_argnums=(1, 2)).trace(
            sds((32, 1, 4096), jnp.bfloat16, sharding=one), pool, pool,
            sds((32, cols), jnp.int32, sharding=one),
            sds((32,), jnp.int32, sharding=one),
            sds((32,), jnp.int32, sharding=one)).lower(
                lowering_platforms=("tpu",)).compile()
        text = c.as_text()
        out[f"commanda_layer_{kind}"] = {
            "pool_bytes": POOL * 2,
            "pool_layout": list(c.input_formats[0][1].layout.major_to_minor),
            "pool_sized": pool_sized(text),
            "temp_bytes": int(c.memory_analysis().temp_size_in_bytes),
            "alias_bytes": int(c.memory_analysis().alias_size_in_bytes),
            "custom_calls": text.count('"tpu_custom_call"'),
            "kernels": sorted(set(re.findall(
                r"%(paged_attention\w*?)(?:\.\d+)? = ", text))),
            "keys_per_program": po.paged_keys_per_program(
                16, 8, 128, jnp.bfloat16, cols, 16)}
    except Exception as e:
        out[f"commanda_layer_{kind}"] = f"{type(e).__name__}: {e}"[:600]

# the sdar cell's attention layer at its own sizes (PR 37: 32 query heads
# over 4 key/value heads of 128, 32 slots x a block of 4 rows, pools of
# 1 + 32 x 304 blocks behind 304 table columns), donated: the block span is
# the one custom call, under the paged kernel's name, and the pools are
# written in place
from paddle_tpu.models.sdar_moe import SdarMoeAttention, SdarMoeConfig

sdar_attn = SdarMoeAttention(SdarMoeConfig(dtype="bfloat16",
                                           num_hidden_layers=1))
sdar_attn.eval()

def sdar_layer(u, kp, vp, bt, off, sl):
    with ag.no_grad(), lazy.lazy_guard(False):
        y, (nk, nv) = sdar_attn(
            u, off[:, None] + jnp.arange(4, dtype=jnp.int32)[None],
            cache=(kp, vp), cache_offset=off, seq_lens=sl, block_tables=bt,
            paged_kernel="pallas")
    return y, nk, nv

try:
    blocks = 1 + 32 * 304
    pool = sds((blocks, 16, 512), jnp.bfloat16, sharding=one)
    c = jax.jit(sdar_layer, donate_argnums=(1, 2)).trace(
        sds((32, 4, 2048), jnp.bfloat16, sharding=one), pool, pool,
        sds((32, 304), jnp.int32, sharding=one),
        sds((32,), jnp.int32, sharding=one),
        sds((32,), jnp.int32, sharding=one)).lower(
            lowering_platforms=("tpu",)).compile()
    text = c.as_text()
    out["sdar_layer"] = {
        "pool_bytes": blocks * 16 * 512 * 2,
        "pool_layout": list(c.input_formats[0][1].layout.major_to_minor),
        "pool_sized": pool_sized(text),
        "temp_bytes": int(c.memory_analysis().temp_size_in_bytes),
        "alias_bytes": int(c.memory_analysis().alias_size_in_bytes),
        "custom_calls": text.count('"tpu_custom_call"'),
        "kernels": sorted(set(re.findall(
            r"%(paged_attention\w*?)(?:\.\d+)? = ", text))),
        "plan": list(po._paged_plan(16, 4, 128, jnp.bfloat16, 4, 304, 8))}
except Exception as e:
    out["sdar_layer"] = f"{type(e).__name__}: {e}"[:600]

# the serving engines' own executables at toy depth and the cells'
# vocabularies and slots: sampling finds its thresholds by selection (PR 31),
# so neither `serving_decode` nor `serving_prefill` may hold a sort whose
# operand has the vocabulary as its minor dimension (the parent's two cost
# 36 % of the xing4 cell's decode step), and each filter sits behind its
# `conditional`
from paddle_tpu.models import GPTModel, Xing4Config, Xing4Model
from paddle_tpu.serving.engine import GenerationEngine

def engine_avals(eng, L):
    av = lambda a: sds(np.shape(a), a.dtype, sharding=one)
    head = (tuple(av(a) for a in eng._state_arrays()),
            tuple(av(a) for a in eng._k), tuple(av(a) for a in eng._v))
    decode = head + tuple(av(a) for a in (
        eng._last_tokens, eng._cur_lens, eng._keys, eng._gen_idx,
        eng._temps, eng._top_ks, eng._top_ps, eng._active,
        eng._block_tables))
    row = lambda a: sds((1,), a.dtype, sharding=one)
    prefill = head + (sds((1, L), jnp.int32, sharding=one),
                      row(eng._cur_lens), row(eng._cur_lens),
                      av(eng._block_tables[:1]), av(eng._keys[0]),
                      row(eng._temps), row(eng._top_ks), row(eng._top_ps))
    return {"decode": (eng._decode_pure, decode),
            "prefill": (eng._prefill_pure, prefill)}

def vocab_sorts(text, V):
    return [ln.strip()[:120] for ln in text.splitlines()
            if re.search(r" sort\(", ln)
            and re.search(rf",{V}\]", ln.split(" sort(")[0])]

toys = {
    "gpt": (50304, lambda V: GPTModel(GPTConfig(
        n_layer=1, n_head=2, d_model=128, seq_len=256, vocab_size=V,
        dtype="bfloat16"))),
    "xing4": (131072, lambda V: Xing4Model(Xing4Config.preset(
        "tiny", vocab_size=V, num_hidden_layers=2, dtype="bfloat16"))),
}
for fam, (V, build) in toys.items():
    try:
        m = build(V)
        m.eval()
        eng = GenerationEngine(m, max_batch_size=32, buckets=(64,),
                               max_seq_len=256, rng_seed=0)
        for step, (fn, avals) in engine_avals(eng, 64).items():
            text = jax.jit(fn).trace(*avals).lower(
                lowering_platforms=("tpu",)).compile().as_text()
            out[f"sampling_{fam}_{step}"] = {
                "vocab_sorts": vocab_sorts(text, V),
                "conditionals": len(re.findall(r" conditional\(", text)),
                "whiles": len(re.findall(r" while\(", text)),
                "kernel": eng.paged_kernel,
                "prefill_kernel": eng.stats()["prefill_kernel"],
                "kernels": sorted(re.findall(
                    r"%(flash_prefill|\w*paged_attention\w*?)(?:\.\d+)? = ",
                    text))}
    except Exception as e:
        out[f"sampling_{fam}"] = f"{type(e).__name__}: {e}"[:600]

# ... and the engines' own steps at toy depth with expert widths Mosaic can
# tile: `serving_decode` (a block decoder's block step too) holds the
# kernel's calls, two an expert layer, and no `ragged-dot`; a prompt's rows
# (more than 32 a group) keep `ragged-dot` in `serving_prefill`
from paddle_tpu.models import SdarMoeConfig, SdarMoeModel
moe_toys = {
    "xing4": (lambda: Xing4Model(Xing4Config.preset(
        "tiny", num_hidden_layers=2, hidden_size=128,
        moe_intermediate_size=128, dtype="bfloat16")), 32, 256, 512),
    "sdar": (lambda: SdarMoeModel(SdarMoeConfig.preset(
        "tiny", num_hidden_layers=2, hidden_size=128, head_dim=64,
        moe_intermediate_size=128, max_position_embeddings=1024,
        dtype="bfloat16")), 8, 512, 1024),
}
for fam, (build, slots, bucket, max_len) in moe_toys.items():
    try:
        m = build()
        m.eval()
        eng = GenerationEngine(m, max_batch_size=slots, buckets=(bucket,),
                               max_seq_len=max_len, rng_seed=0)
        av = lambda a: sds(np.shape(a), a.dtype, sharding=one)
        steps = engine_avals(eng, bucket)
        head = steps["prefill"][1][:3]
        steps["decode"] = (
            eng._decode_pure if eng.generation is None else eng._block_pure,
            head + tuple(av(getattr(eng, n)) for n in eng._slot_state))
        for step, (fn, avals) in steps.items():
            text = jax.jit(fn).trace(*avals).lower(
                lowering_platforms=("tpu",)).compile().as_text()
            out[f"grouped_engine_{fam}_{step}"] = {
                "kind": eng.stats()["moe_grouped_kernel"],
                "expert_layers": len([l for l in m.sublayers()
                                      if hasattr(l, "grouped_shapes")]),
                "kernels": len(re.findall(
                    r"%grouped_matmul[\w.]* = ", text)),
                "ragged": len(re.findall(
                    r"%ragged-dot-none[\w.]* = ", text))}
    except Exception as e:
        out[f"grouped_engine_{fam}"] = f"{type(e).__name__}: {e}"[:600]

mesh = Mesh(np.array(devs).reshape(2, 2), ("dp", "mp"))
lazy.set_spmd_mesh(mesh)
b = sds((8, 1024, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))
compile_("flash_grad_dp2_mp2", jax.grad(loss, argnums=(0, 1, 2)), b, b, b)
print(json.dumps(out))
"""


def test_aot_compile_for_v5e():
    """XLA + Mosaic compile every kernel family for a described v5e —
    single chip, the 'mp' serving mesh and the (dp2, mp2) train mesh —
    with no collective around the per-shard custom calls."""
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _AOT_CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    if "unavailable" in res:
        pytest.skip("libtpu cannot describe a v5e here: "
                    + res["unavailable"])
    assert res.pop("device_kind") == "TPU v5 lite"
    too_big = res.pop("paged_block_4MiB")
    assert isinstance(too_big, str) and "vmem" in too_big, too_big
    assert not po.paged_tileable(256, 64, jnp.float32, 64)[0]
    assert po.paged_tileable(256, 32, jnp.float32, 64)[0]
    assert po._paged_plan(32, 64, 256, jnp.float32)[0] == 1
    ok, why = po.paged_tileable(80, 8, jnp.float32, 12)
    assert not ok and "128-lane" in why
    # PR 36 added serving's own flash forward and touched no training
    # kernel: the body of `flash_fwd` at the training cell's shape is the
    # parent's (commit bbcbf68); the new kernel's is pinned further down
    assert res.pop("flash_fwd_body_sha256", None) == FLASH_FWD_BODY_SHA256, \
        res.get("flash_fwd_body")
    bad = {k: v for k, v in res.items() if not isinstance(v, dict)
           and k != "xing4_mla_paged_body"}
    assert not bad, bad
    assert res["flash_grad"] == {"custom_calls": 3, "collectives": 0}
    assert res["flash_grad_dp2_mp2"] == {"custom_calls": 3,
                                         "collectives": 0}
    assert res["paged_mp4"] == {"custom_calls": 1, "collectives": 0}
    assert all(v["custom_calls"] == 1 for k, v in res.items()
               if k.startswith("paged_"))
    # both gpt serving cells: one custom call a layer, 256 keys a program
    # PR 35 taught the heads kernel grouped queries and a window; the body
    # Mosaic is handed at the chat cell's geometry is PR 33's (commit
    # 4abdfaf), byte for byte once paths and lines are set aside. A PR that
    # means to change the multi-head kernel replaces the digest.
    assert res["paged_chat_cell"].pop("body_sha256") \
        == PAGED_CHAT_BODY_SHA256, res.get("paged_chat_cell_body")
    for cell in ("paged_chat_cell", "paged_long_prefill_cell"):
        assert res[cell] == {"custom_calls": 1, "collectives": 0,
                             "keys_per_program": 256}, res[cell]
    new = res["flash_prefill_2048"]
    assert new.pop("body_sha256") == FLASH_PREFILL_BODY_SHA256, \
        res.get("flash_prefill_2048_body", new)
    assert new == {"custom_calls": 1, "collectives": 0, "plan": [128, 32]}
    _SERVE_LAYERS.update((k, v) for k, v in res.items()
                         if k.startswith(("serve_layer_", "xing4_",
                                          "sampling_", "commanda_", "sdar_",
                                          "prefill_layer_", "grouped_")))


_SERVE_LAYERS: dict = {}
PAGED_CHAT_BODY_SHA256 = (
    "272c8a5eeab65e345bf54fd326f0eb7606a7194b06f5a590744a4a01389a830e")
FLASH_FWD_BODY_SHA256 = (
    "c580f9859193939218cf0161a111bb338f19d97944b6e07bf801c0e54349e4b8")
FLASH_PREFILL_BODY_SHA256 = (
    "ec2d3c3f4aeb5e5403586bb0efbf4613e4f6414dda9fdc23bcadfecb90ddae02")
LATENT_BODY_SHA256 = (
    "4d8e0df8e25229452bc5f7e3359b5d94b5833ef70664c81897e04bccc6ab27ac")


def test_latent_kernel_and_grouped_matmul_compile_for_v5e():
    """The xing4 cell's two kernels at its own sizes (the AOT child's
    result of the test above): Mosaic takes `mla_paged_attention`, and
    XLA:TPU lowers `lax.ragged_dot` to ITS grouped matmul (a custom call
    with a metadata call in front, no dense [E, m, k] expansion) when the
    precision is named as `nn/moe/dropless.py` names it."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    mla = dict(_SERVE_LAYERS["xing4_mla_paged"])
    # PR 33 rewrote the heads kernel and left this one as it was: the body
    # Mosaic is handed is the one PR 32's tree (commit ac7b8f9) hands it,
    # byte for byte once paths and lines are set aside. A PR that means to
    # change the latent kernel replaces the digest.
    assert mla.pop("body_sha256") == LATENT_BODY_SHA256, \
        _SERVE_LAYERS.get("xing4_mla_paged_body", mla)
    assert mla == {"custom_calls": 1, "collectives": 0,
                   "keys_per_program": 128}
    assert _SERVE_LAYERS["xing4_grouped_matmul"] == {"custom_calls": 2,
                                                     "collectives": 0}


GROUPED_TILES = {"sdar_gate_up": [32, 1024, 1536],
                 "sdar_down": [32, 768, 2048],
                 "commanda_gate_up": [32, 256, 8192],
                 "commanda_down": [32, 512, 4096],
                 "xing4_gate_up": [32, 896, 2048],
                 "xing4_down": [32, 512, 3584]}


@pytest.mark.parametrize("cell", list(GROUPED_TILES))
def test_grouped_matmul_kernel_compiles_for_v5e(cell):
    """ISSUE 38: the expert layers' decode matmuls of the three expert
    cells through `grouped_matmul`, compiled for the described v5e (the AOT
    child's result of the test above): one Mosaic custom call under the
    kernel's name, no `ragged-dot`, the weights read as they lie (nothing in
    the program makes an array of the bank's shape) and the tiles PERF.md
    names."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    assert _SERVE_LAYERS[f"grouped_{cell}"] == {
        "custom_calls": 1, "kernels": 1, "ragged": 0, "bank_copies": [],
        "plan": GROUPED_TILES[cell]}


@pytest.mark.parametrize("family", ["xing4", "sdar"])
def test_serving_decode_runs_its_experts_through_the_kernel_on_v5e(family):
    """A toy engine of each kind of decoder compiled for the described v5e:
    `serving_decode` (the block step of a block-diffusion decoder) holds two
    `grouped_matmul` calls an expert layer and no `ragged-dot`; the prompt's
    rows, more than 32 a group, keep `ragged-dot` in `serving_prefill`."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    assert f"grouped_engine_{family}" not in _SERVE_LAYERS, \
        _SERVE_LAYERS[f"grouped_engine_{family}"]
    decode = _SERVE_LAYERS[f"grouped_engine_{family}_decode"]
    n = decode["expert_layers"]
    assert n >= 1 and decode == {"kind": "pallas", "expert_layers": n,
                                 "kernels": 2 * n, "ragged": 0}
    # (XLA:TPU takes some of a toy's grouped matmuls in another form: at
    # least one a layer stays its `ragged-dot`)
    prefill = dict(_SERVE_LAYERS[f"grouped_engine_{family}_prefill"])
    assert n <= prefill.pop("ragged") <= 2 * n
    assert prefill == {"kind": "pallas", "expert_layers": n, "kernels": 0}


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["gpt", "xing4"])
def test_serving_steps_hold_no_sort_over_the_vocabulary_on_v5e(family, step):
    """`serving_decode` and `serving_prefill` of both engines, at toy depth
    and the cells' vocabularies (50,304 and 131,072 ids; 32 slots, one
    prompt), compiled for the described v5e (the AOT child's result of the
    test above): no `sort` whose operand has the vocabulary as its minor
    dimension — the parent of PR 31 held two a step, `%sort` over
    f32[32,131072] being the first device group of the xing4 cell — and
    each filter's passes (a `while`) behind their own `conditional`."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    got = _SERVE_LAYERS.get(f"sampling_{family}_{step}",
                            _SERVE_LAYERS.get(f"sampling_{family}"))
    assert isinstance(got, dict), got
    assert got["vocab_sorts"] == [], got
    assert got["conditionals"] >= 2 and got["whiles"] >= 2, got


@pytest.mark.parametrize("T", [1, 5])  # decode, spec verify (K+1)
def test_serving_layer_writes_its_kv_rows_in_place_on_v5e(T):
    """The serving cell's layer, compiled for the described v5e with
    donated pools (gpt3-1.3b's: 3,073 blocks x 16 x 32 heads x 64, 32
    slots, 128 table columns): the pools enter row-major, nothing of a
    pool's size exists but the pools themselves and the in-place write,
    and the temporaries stay under one pool. The parent of PR 28 (a 4-D
    pool, an element scatter) compiled to 14 pool-sized copies, reshapes
    and transposes and 806 MB of temporaries for this layer — 88 % of its
    decode step on the chip. Reads the AOT child's result of the test
    above (one libtpu load for the file)."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    got = _SERVE_LAYERS[f"serve_layer_T{T}"]
    assert isinstance(got, dict), got
    assert got["custom_calls"] == 1
    assert got["pool_layout"] == [0, 1, 2], got  # (a) row-major on entry
    extra = {op: n for op, n in got["pool_sized"].items()
             if op not in ("parameter", "bitcast", "write", "scatter",
                           "dynamic-update-slice")}
    assert not extra, got  # (b) no pool-sized copy / reshape / transpose
    assert got["pool_sized"].get("write", 0) \
        + got["pool_sized"].get("dynamic-update-slice", 0) >= 2, got
    assert got["temp_bytes"] < got["pool_bytes"], got  # (c)
    assert got["alias_bytes"] >= 2 * got["pool_bytes"], got  # donated


@pytest.mark.parametrize("L", [256, 512, 1024, 2048])
def test_prompt_span_reads_through_flash_prefill_on_v5e(L):
    """The two gpt cells' attention layer on a prompt span (gpt3-1.3b: one
    slot's bucket of L rows, 32 heads x 64, the cell's own pools, donated),
    compiled for the described v5e (PR 36; the AOT child's result of the
    test above): ONE custom call, `flash_prefill`, and no float32 array of
    every head's scores `[32, L, 2048]` — which the gather path at the same
    shapes does hold (the parent's 88 of a 147 ms prefill), so the search
    would find them. The pools are still written in place and the layer's
    temporaries are the bucket's activations, far under one head's scores."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    got = _SERVE_LAYERS[f"prefill_layer_L{L}"]
    assert isinstance(got, dict), got
    assert got["custom_calls"] == 1 and got["kernels"] == ["flash_prefill"]
    assert got["score_arrays"] == [], got
    assert got["alias_bytes"] >= 2 * got["pool_bytes"], got
    assert got["temp_bytes"] < 32 * L * 2048 * 4 // 8, got
    gather = _SERVE_LAYERS["prefill_layer_L2048_gather"]
    assert isinstance(gather, dict), gather
    assert gather["custom_calls"] == 0 and gather["score_arrays"], gather
    assert gather["temp_bytes"] > 32 * 2048 * 2048 * 4, gather


def test_serving_prefill_holds_one_flash_prefill_a_layer_on_v5e():
    """The engine's own executables (the toy gpt engine of the AOT child:
    one layer, 32 slots, one bucket of 64): the prefill kernel follows the
    paged kernel to "pallas", `serving_prefill` holds one `flash_prefill`
    custom call a layer and no paged kernel, `serving_decode` the
    reverse; the xing4 engine's prefill is its own forward's and resolves
    to "xla" without a fallback."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    pre, dec = (_SERVE_LAYERS.get(f"sampling_gpt_{step}",
                                  _SERVE_LAYERS.get("sampling_gpt"))
                for step in ("prefill", "decode"))
    assert isinstance(pre, dict) and isinstance(dec, dict), (pre, dec)
    assert pre["kernel"] == "pallas" and pre["prefill_kernel"] == "pallas"
    assert pre["kernels"] == ["flash_prefill"], pre
    assert dec["kernels"] == ["paged_attention"], dec
    x4 = _SERVE_LAYERS.get("sampling_xing4_prefill",
                           _SERVE_LAYERS.get("sampling_xing4"))
    assert isinstance(x4, dict), x4
    assert x4["prefill_kernel"] == "xla" and x4["kernels"] == [], x4


@pytest.mark.parametrize("kind", ["window", "full"])
def test_window_and_full_pools_are_row_major_and_written_in_place_on_v5e(kind):
    """The command-a-plus cell's two kinds of attention layer, compiled for
    the described v5e with donated pools at the cell's sizes (PR 35; the AOT
    child's result of the test above): a window layer's ring pools (1 + 32 x
    257 blocks of 16 rows of 8 x 128) and a full layer's (1 + 32 x 560)
    enter row-major, nothing of a pool's size exists but the pools and the
    in-place write, the temporaries stay under one pool, and the layer's one
    custom call carries its kind's name: 16 query heads a key/value head
    through `paged_attention_window` / `paged_attention`, 512 keys a
    program."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    got = _SERVE_LAYERS[f"commanda_layer_{kind}"]
    assert isinstance(got, dict), got
    assert got["custom_calls"] == 1 and got["keys_per_program"] == 512
    assert got["kernels"] == ["paged_attention_window" if kind == "window"
                              else "paged_attention"], got
    assert got["pool_layout"] == [0, 1, 2], got
    extra = {op: n for op, n in got["pool_sized"].items()
             if op not in ("parameter", "bitcast", "write", "scatter",
                           "dynamic-update-slice")}
    assert not extra, got
    assert got["pool_sized"].get("write", 0) \
        + got["pool_sized"].get("scatter", 0) \
        + got["pool_sized"].get("dynamic-update-slice", 0) >= 2, got
    assert got["temp_bytes"] < got["pool_bytes"], got
    assert got["alias_bytes"] >= 2 * got["pool_bytes"], got


def test_block_span_layer_is_one_paged_kernel_written_in_place_on_v5e():
    """The sdar cell's attention layer (PR 37), compiled for the described
    v5e with donated pools at the cell's sizes (the AOT child's result): a
    block of 4 rows a slot, 8 query heads a key/value head, meets its keys
    through ONE custom call under the paged kernel's name (the block span is
    the same kernel, so the accepted roofline reader finds it); two
    key/value heads' 8 x 4 query rows each (64 rows against 256 lanes) a
    dot, 512 keys a program; the pools enter row-major and are written in
    place."""
    if not _SERVE_LAYERS:
        pytest.skip("test_aot_compile_for_v5e did not compile here")
    got = _SERVE_LAYERS["sdar_layer"]
    assert isinstance(got, dict), got
    assert got["custom_calls"] == 1 and got["kernels"] == ["paged_attention"]
    assert got["plan"] == [32, 256, 64], got
    assert got["pool_layout"] == [0, 1, 2], got
    extra = {op: n for op, n in got["pool_sized"].items()
             if op not in ("parameter", "bitcast", "write", "scatter",
                           "dynamic-update-slice")}
    assert not extra, got
    assert got["temp_bytes"] < got["pool_bytes"], got
    assert got["alias_bytes"] >= 2 * got["pool_bytes"], got
