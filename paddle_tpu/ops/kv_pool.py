"""paddle_tpu.ops.kv_pool — the paged KV pool's form on the device.

One K (or V) pool of one layer is ``[num_blocks, block_size, H * Dh]``:
heads merged into the last axis, head ``h`` at lanes ``h*Dh:(h+1)*Dh``
(heads major, so an 'mp' shard is a contiguous run of heads:
``PartitionSpec(None, None, "mp")``). This module is the only place that
knows it: allocation, the in-place write of a step's new rows, the view
the gather path reads, the 4-D blocks of the KV-handoff payload, and the
layout check behind the ``serving.kv_pool_row_major`` gauge.

Why merged (PERF.md, PR 28): a TPU stores ``bf16[Nb, bs, H, 64]`` with the
BLOCK index minor-most (layout ``{0,3,2,1:T(8,128)(2,1)}``: a 64-wide last
dimension would waste half of every 128-lane tile), so whatever wants rows,
a scatter or the Mosaic kernel, gets a whole-pool relayout in front and
another behind. ``[Nb, bs, H*Dh]`` is plain row-major there, and the write
below compiles to an in-place fusion on the donated buffer. Inside a jitted
step no 4-D view of a whole pool is ever formed: on the chip that reshape
is a relayout, not a bitcast. The host side (handoff payloads, tests)
reshapes freely.

A decoder says what its layers cache through a :class:`CacheSpec`
(``decoder.kv_cache_spec()``). ``"heads"`` is the above. ``"latent"`` (MLA)
is ONE pool a layer, ``[num_blocks, block_size, W]``: a token's row is its
normalised latent ``c_kv`` (``rank`` lanes) followed by the rotated key all
heads share (``rope_dim`` lanes), zero-padded to ``W``, the next multiple
of 128 lanes, so that the row is whole lane tiles (512 + 64 -> 640: stored
row-major on a v5e like the merged form, AOT, PERF.md) and the decode kernel
scores a block with one dot over the row.
"""
from __future__ import annotations

import collections

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


_LANES = 128


class CacheSpec:
    """What a decoder's layers keep a token: ``kind`` "heads" with
    ``layers`` = [(n_kv_head, head_dim), ...] (a K and a V pool a layer;
    the heads of a ROW, which grouped-query attention reads with more
    query heads than that), or "latent" with ``layers`` = [(rank,
    rope_dim), ...] (one pool a layer).

    ``windows`` (heads only) says per layer how many keys a query may see,
    itself among them, or None for every earlier key. A window layer's
    state is a RING: a slot keeps ``ring_blocks`` = window / block_size + 1
    blocks of it whatever its length, position ``p`` lives in ring block
    ``(p // block_size) % ring_blocks`` (one block over the window, so the
    block a new row lands in never holds a key that row may still see), and
    its pools are sized by the window, not by ``max_seq_len``. A slot's
    ring blocks are its own from engine build: blocks ``1 + slot * ring
    ..``, block 0 the garbage block as in every pool. All window layers of
    a decoder share one window."""

    def __init__(self, kind, layers, windows=None, q_per_kv=1):
        if kind not in ("heads", "latent"):
            raise ValueError(f"unknown cache kind {kind!r}")
        self.kind = kind
        # query heads that read each head of a row (grouped queries): the
        # pools do not care, the paged kernel's plan does
        self.q_per_kv = int(q_per_kv)
        self.layers = [tuple(int(x) for x in l) for l in layers]
        self.windows = [None if w is None else int(w) for w in (
            windows or [None] * len(self.layers))]
        distinct = {w for w in self.windows if w is not None}
        if len(self.windows) != len(self.layers) or len(distinct) > 1 \
                or any(w < 1 for w in distinct) \
                or (distinct and kind != "heads"):
            raise ValueError(
                f"CacheSpec: windows {windows!r} must give every layer of "
                "a 'heads' cache None or the one window (>= 1) the "
                "decoder's window layers share")
        self.window = distinct.pop() if distinct else None

    def row_width(self, layer=0):
        """Lanes of one pool's row in this layer."""
        a, b = self.layers[layer]
        if self.kind == "heads":
            return a * b
        return -(-(a + b) // _LANES) * _LANES

    def heads(self, layer=0):
        """The heads a pool's row holds (what an 'mp' shard divides): a
        latent row is shared by every head."""
        return self.layers[layer][0] if self.kind == "heads" else 1

    def window_layers(self):
        return [i for i, w in enumerate(self.windows) if w is not None]

    def ring_blocks(self, block_size):
        """Blocks of one slot's ring in a window layer (0: no such
        layer)."""
        return ring_blocks(self.window, block_size) if self.window else 0

    def layer_blocks(self, layer, num_blocks, block_size, slots):
        """Blocks of this layer's pools: the engine's ``num_blocks`` for a
        full layer, the garbage block and every slot's ring for a window
        layer."""
        if self.windows[layer] is None:
            return int(num_blocks)
        return 1 + int(slots) * self.ring_blocks(block_size)

    def allocate(self, num_blocks, block_size, dtype, mesh=None, slots=0):
        """``(first, second)`` pools of every layer: the K and the V pools
        of a "heads" cache (heads over 'mp' on a mesh they divide; a window
        layer's sized by ``slots`` rings, see :meth:`layer_blocks`); the
        latent pools and none of a "latent" one (no mesh: the engine
        refuses one for this kind)."""
        if self.kind == "latent":
            return ([jnp.zeros((num_blocks, block_size, self.row_width(i)),
                               dtype) for i in range(len(self.layers))], [])
        sharding = None
        if mesh is not None:
            mp = int(dict(zip(mesh.axis_names,
                              mesh.devices.shape)).get("mp", 1))
            sharding = NamedSharding(mesh, pspec(
                mp > 1 and all(h % mp == 0 for h, _ in self.layers)))

        def pools():
            made = [zeros(self.layer_blocks(i, num_blocks, block_size,
                                            slots), block_size, h, dh, dtype)
                    for i, (h, dh) in enumerate(self.layers)]
            if sharding is not None:
                made = [jax.device_put(p, sharding) for p in made]
            return made

        return pools(), pools()

    def layer_tables(self, block_tables, block_size):
        """What each layer's forward addresses its pools through. One kind
        of layer: the engine's table as it is. Full and window layers: the
        engine's table row is the full layers' columns followed by the
        slot's ``ring_blocks`` ring columns, and this gives every layer its
        own part."""
        if self.window is None:
            return block_tables
        ring = self.ring_blocks(block_size)
        full, rings = block_tables[:, :-ring], block_tables[:, -ring:]
        return [full if w is None else rings for w in self.windows]

    def describe(self):
        """Every kind of layer the cache has, with how many of each: "3 x
        heads: a K and a V row of 8 x 128 = 1024 a layer, window 4096 (a
        ring of 257 blocks a slot at block 16); 1 x heads: ..., every
        key" — for stats() and the layout explainer."""
        kinds = collections.Counter(zip(self.layers, self.windows))
        said = []
        for ((a, b), w), n in kinds.items():
            if self.kind == "heads":
                what = (f"heads: a K and a V row of {a} x {b} = {a * b} a "
                        "layer")
                if self.window is not None:
                    what += (", every earlier key" if w is None else
                             f", the last {w} keys in a ring")
            else:
                what = (f"latent: one row of "
                        f"{self.row_width(self.layers.index((a, b)))} a "
                        f"layer ({a} latent + {b} rotated key, padded to "
                        "whole lane tiles)")
            said.append(what if len(kinds) == 1 else f"{n} x {what}")
        return "; ".join(said)


def ring_blocks(window, block_size):
    """Blocks of a slot's ring for ``window`` keys: one over the window."""
    return -(-int(window) // int(block_size)) + 1


def ring_table(slots, ring):
    """The ring columns of every slot's table row, numpy ``[slots, ring]``:
    slot s owns blocks ``1 + s * ring ..`` of each window layer's pools
    from engine build to the end (nothing allocates or frees them)."""
    return (1 + np.arange(slots, dtype=np.int32)[:, None] * ring
            + np.arange(ring, dtype=np.int32)[None])


def zeros(num_blocks, block_size, num_heads, head_dim, dtype):
    """A fresh pool. Block 0 is the reserved garbage block."""
    return jnp.zeros((num_blocks, block_size, num_heads * head_dim), dtype)


def pspec(heads_sharded):
    """Placement of a pool on a serving mesh: heads over 'mp' when they
    divide, else replicated."""
    return PartitionSpec(None, None, "mp") if heads_sharded \
        else PartitionSpec()


def merged(pool):
    """``pool`` in the device form. A 4-D ``[Nb, bs, H, Dh]`` pool (the
    public op's older calling convention) is merged on entry: free on the
    CPU, a relayout for whoever still passes 4-D on the chip."""
    if pool.ndim == 4:
        return pool.reshape(pool.shape[0], pool.shape[1], -1)
    return pool


def span_rows(block_tables, offsets, seq_lens, span, block_size):
    """Where the ``span`` new rows of every slot go: (block ids, rows in
    the block), each ``[B * span]`` int32. Row ``offsets[b] + t`` lands in
    the slot's own block through its table row; rows outside
    ``[0, seq_lens[b])`` (bucket padding, inactive decode lanes) all land
    on row 0 of the reserved garbage block 0, so indices repeat there and
    only there."""
    bt = block_tables.astype(jnp.int32)
    bs = jnp.int32(block_size)
    rows = (offsets.astype(jnp.int32)[:, None]
            + jnp.arange(span, dtype=jnp.int32)[None])
    blk = jnp.minimum(rows // bs, jnp.int32(bt.shape[1] - 1))
    phys = jnp.take_along_axis(bt, blk, axis=1)
    writable = rows < seq_lens.astype(jnp.int32)[:, None]
    zero = jnp.zeros_like(rows)
    return (jnp.where(writable, phys, zero).reshape(-1),
            jnp.where(writable, rows % bs, zero).reshape(-1))


def write_rows(pool, rows, block_ids, row_ids):
    """``pool`` with ``rows`` ``[N, H*Dh]`` written at ``(block_ids[n],
    row_ids[n])``: a row scatter with a two-part index on the pool as it
    is, no reshape for the compiler to turn into a relayout. On a donated
    pool it updates the buffer in place (tests/test_tpu_lowering.py).
    Repeated indices (the garbage row) make no promise about which row
    wins, as the element scatter before it made none."""
    return pool.at[block_ids, row_ids].set(rows.astype(pool.dtype))


def ring_span_rows(ring_tables, offsets, seq_lens, span, block_size,
                   window):
    """:func:`span_rows` for a window layer's ring: row ``offsets[b] + t``
    = position p lands in the slot's ring column ``(p // block_size) %
    ring``. Only the rows a later query can still see are written, ``p >=
    seq_lens[b] - window`` (of a prompt longer than the window the head
    never lands, so no two rows of one call meet in a ring block); the rest
    and the padding go to the garbage row."""
    bt = ring_tables.astype(jnp.int32)
    bs = jnp.int32(block_size)
    sl = seq_lens.astype(jnp.int32)[:, None]
    rows = (offsets.astype(jnp.int32)[:, None]
            + jnp.arange(span, dtype=jnp.int32)[None])
    phys = jnp.take_along_axis(
        bt, (rows // bs) % jnp.int32(bt.shape[1]), axis=1)
    writable = (rows < sl) & (rows >= sl - jnp.int32(window))
    zero = jnp.zeros_like(rows)
    return (jnp.where(writable, phys, zero).reshape(-1),
            jnp.where(writable, rows % bs, zero).reshape(-1))


def ring_positions(seq_lens, ring, block_size):
    """The position each row of a slot's gathered ring view ``[B, ring *
    block_size]`` holds when the slot has ``seq_lens[b]`` rows: column c
    holds the newest logical block ``lb <= last`` with ``lb % ring == c``
    (negative where the ring has not come round to it: nothing written)."""
    last = (seq_lens.astype(jnp.int32)[:, None] - 1) // jnp.int32(block_size)
    col = jnp.arange(ring, dtype=jnp.int32)[None]
    block = last - (last - col) % jnp.int32(ring)
    return (block[:, :, None] * jnp.int32(block_size)
            + jnp.arange(block_size, dtype=jnp.int32)[None, None]
            ).reshape(seq_lens.shape[0], ring * block_size)


def write_span(k_pool, v_pool, k, v, block_tables, offsets, seq_lens,
               window=None):
    """Both pools with a step's new rows ``k``, ``v`` ``[B, T, H, Dh]``
    written through the block tables: :func:`span_rows` (a window layer's
    ring: :func:`ring_span_rows`), then :func:`write_rows` for each."""
    B, T = k.shape[0], k.shape[1]
    if window is None:
        blk, row = span_rows(block_tables, offsets, seq_lens, T,
                             k_pool.shape[1])
    else:
        blk, row = ring_span_rows(block_tables, offsets, seq_lens, T,
                                  k_pool.shape[1], window)
    return (write_rows(k_pool, k.reshape(B * T, -1), blk, row),
            write_rows(v_pool, v.reshape(B * T, -1), blk, row))


def latent_view(pool, block_tables):
    """Every slot's logical ``[B, M*bs, W]`` view of a latent pool."""
    B, M = block_tables.shape
    blocks = jnp.take(pool, block_tables.reshape(-1).astype(jnp.int32),
                      axis=0)
    return blocks.reshape(B, M * pool.shape[1], pool.shape[2])


def gather_view(pool, block_tables, num_heads):
    """Every slot's logical ``[B, M*bs, H, Dh]`` view of the pool, whole
    blocks gathered in table order (the XLA read path; the result is the
    size of the view, never of the pool)."""
    B, M = block_tables.shape
    blocks = jnp.take(pool, block_tables.reshape(-1).astype(jnp.int32),
                      axis=0)
    return blocks.reshape(B, M * pool.shape[1], num_heads, -1)


def export_blocks(pool, block_ids, num_heads):
    """Host copy of the given blocks as the handoff payload carries them:
    numpy ``[n, block_size, H, Dh]`` (the reshape is on the host, free)."""
    idx = jnp.asarray(np.asarray(block_ids, np.int32))
    got = np.asarray(jnp.take(pool, idx, axis=0))
    return got.reshape(got.shape[0], got.shape[1], num_heads, -1)


def import_blocks(pool, block_ids, blocks, put=jnp.asarray):
    """``pool`` with payload ``blocks`` ``[n, block_size, H, Dh]`` written
    at ``block_ids``. ``put`` places the host array (a mesh engine passes
    its replicated placement)."""
    idx = jnp.asarray(np.asarray(block_ids, np.int32))
    host = np.asarray(blocks)
    host = host.reshape(host.shape[0], host.shape[1], -1)
    return pool.at[idx].set(put(host).astype(pool.dtype))


def block_shape(pool, num_heads):
    """``(block_size, H, Dh)`` of one payload block of this pool."""
    return (int(pool.shape[1]), int(num_heads),
            int(pool.shape[2]) // int(num_heads))


def device_layout(pool):
    """The pool's major-to-minor dimension order on its device, where jax
    exposes it (``array.format``), else None. ``(0, 1, 2)`` is row-major:
    a row of ``H*Dh`` is contiguous and a block is ``block_size`` of them."""
    try:
        order = pool.format.layout.major_to_minor
    except Exception:  # no format on this array / backend
        return None
    return None if order is None else tuple(int(d) for d in order)
