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

A "heads" cache may also hold STATE layers (``CacheSpec(states=...)``): a
layer of linear attention keeps no row a token but one fixed-size state a
slot — a recurrent state ``[Hv, dk, dv]`` float32 and the tail of its short
convolution, the last ``conv_k - 1`` pre-convolution rows ``[conv_k - 1,
conv_ch]`` — in two pools ``[slots, ...]`` indexed by the slot, not through
a block table. The block pool budgets only the layers that keep rows.
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
    a decoder share one window.

    ``states`` (heads only) says per layer None, or ``(Hv, dk, dv, conv_ch,
    conv_k)`` for a STATE layer (its ``layers`` entry is None): no rows, a
    recurrent state of ``Hv`` heads ``[dk, dv]`` float32 and the last
    ``conv_k - 1`` rows of ``conv_ch`` channels before its short
    convolution, a slot each (see the module's docstring). The engine's
    table row then ends in one column that holds the slot, which a state
    layer's prefill reads (`layer_tables`)."""

    def __init__(self, kind, layers, windows=None, q_per_kv=1, states=None):
        if kind not in ("heads", "latent"):
            raise ValueError(f"unknown cache kind {kind!r}")
        self.kind = kind
        # query heads that read each head of a row (grouped queries): the
        # pools do not care, the paged kernel's plan does
        self.q_per_kv = int(q_per_kv)
        self.states = [None if st is None else tuple(int(x) for x in st)
                       for st in (states or [None] * len(layers))]
        self.layers = [None if l is None else tuple(int(x) for x in l)
                       for l in layers]
        if len(self.states) != len(self.layers) \
                or any((l is None) != (st is not None) or (
                    st is not None and (len(st) != 5 or kind != "heads"))
                       for l, st in zip(self.layers, self.states)) \
                or all(st is not None for st in self.states):
            raise ValueError(
                f"CacheSpec: states {states!r} must give every layer of a "
                "'heads' cache None (a layer that keeps rows) or (Hv, dk, "
                "dv, conv_ch, conv_k) where its `layers` entry is None, "
                "and leave one layer at least that keeps rows")
        self.windows = [None if w is None else int(w) for w in (
            windows or [None] * len(self.layers))]
        distinct = {w for w in self.windows if w is not None}
        if len(self.windows) != len(self.layers) or len(distinct) > 1 \
                or any(w < 1 for w in distinct) \
                or (distinct and kind != "heads") \
                or any(w is not None and st is not None
                       for w, st in zip(self.windows, self.states)):
            raise ValueError(
                f"CacheSpec: windows {windows!r} must give every layer of "
                "a 'heads' cache None or the one window (>= 1) the "
                "decoder's window layers share (a state layer has none)")
        self.window = distinct.pop() if distinct else None

    def rows_layers(self):
        """The layers that keep rows (every layer but the state layers)."""
        return [i for i, st in enumerate(self.states) if st is None]

    def state_layers(self):
        return [i for i, st in enumerate(self.states) if st is not None]

    def row_width(self, layer=None):
        """Lanes of one pool's row in this layer (by default the first layer
        that keeps rows; a state layer keeps none: 0)."""
        if layer is None:
            layer = self.rows_layers()[0]
        if self.layers[layer] is None:
            return 0
        a, b = self.layers[layer]
        if self.kind == "heads":
            return a * b
        return -(-(a + b) // _LANES) * _LANES

    def heads(self, layer=0):
        """The heads a pool's row holds (what an 'mp' shard divides): a
        latent row is shared by every head; a state layer's value heads."""
        if self.states[layer] is not None:
            return self.states[layer][0]
        return self.layers[layer][0] if self.kind == "heads" else 1

    def window_layers(self):
        return [i for i, w in enumerate(self.windows) if w is not None]

    def state_shapes(self, layer, slots):
        """The two pools of a state layer: ``[slots, Hv, dk, dv]`` (the
        recurrent state) and ``[slots, conv_k - 1, conv_ch]`` (the
        convolution's tail)."""
        Hv, dk, dv, ch, ck = self.states[layer]
        return (int(slots), Hv, dk, dv), (int(slots), ck - 1, ch)

    def table_cols(self, blocks_per_slot, block_size):
        """Columns of the engine's table row: the layers' blocks, a window
        layer's ring behind them, and the slot's own column where there are
        state layers."""
        return int(blocks_per_slot) + self.ring_blocks(block_size) \
            + int(bool(self.state_layers()))

    def writes_blocks(self, span, block_size):
        """Does a span of ``span`` rows a slot land in some layer's pools as
        whole blocks (:func:`writes_blocks`)? Only a full layer of a
        "heads" cache can: a latent row is written by the decoder's own
        forward."""
        return self.kind == "heads" and any(
            writes_blocks(span, block_size, self.windows[i])
            for i in self.rows_layers())

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
        layer's sized by ``slots`` rings, see :meth:`layer_blocks`; a state
        layer's state and convolution tail, ``slots`` of each, see
        :meth:`state_shapes`); the latent pools and none of a "latent" one
        (no mesh: the engine refuses one for this kind, and one for state
        layers)."""
        if self.kind == "latent":
            return ([jnp.zeros((num_blocks, block_size, self.row_width(i)),
                               dtype) for i in range(len(self.layers))], [])
        sharding = None
        if mesh is not None:
            mp = int(dict(zip(mesh.axis_names,
                              mesh.devices.shape)).get("mp", 1))
            sharding = NamedSharding(mesh, pspec(
                mp > 1 and all(self.layers[i][0] % mp == 0
                               for i in self.rows_layers())))

        def pool(i, second):
            if self.states[i] is not None:
                state, tail = self.state_shapes(i, slots)
                return jnp.zeros(tail, dtype) if second \
                    else jnp.zeros(state, jnp.float32)
            h, dh = self.layers[i]
            made = zeros(self.layer_blocks(i, num_blocks, block_size, slots),
                         block_size, h, dh, dtype)
            return made if sharding is None \
                else jax.device_put(made, sharding)

        return ([pool(i, False) for i in range(len(self.layers))],
                [pool(i, True) for i in range(len(self.layers))])

    def layer_tables(self, block_tables, block_size):
        """What each layer's forward addresses its pools through. One kind
        of layer: the engine's table as it is. Otherwise the engine's table
        row is the full layers' columns, the slot's ``ring_blocks`` ring
        columns behind them (window layers) and last the slot's own column
        (state layers), and this gives every layer its own part."""
        ring = self.ring_blocks(block_size)
        cols = int(bool(self.state_layers()))
        if not ring and not cols:
            return block_tables
        n = block_tables.shape[1] - ring - cols
        full, rings = block_tables[:, :n], block_tables[:, n:n + ring]
        slot = block_tables[:, n + ring:]
        return [slot if st is not None else full if w is None else rings
                for w, st in zip(self.windows, self.states)]

    def describe(self):
        """Every kind of layer the cache has, with how many of each: "3 x
        heads: a K and a V row of 8 x 128 = 1024 a layer, window 4096 (a
        ring of 257 blocks a slot at block 16); 1 x heads: ..., every
        key" — for stats() and the layout explainer."""
        kinds = collections.Counter(zip(self.layers, self.windows,
                                        self.states))
        said = []
        for (l, w, st), n in kinds.items():
            if st is not None:
                Hv, dk, dv, ch, ck = st
                said.append(f"{n} x state: a slot's [{Hv}, {dk}, {dv}] "
                            f"float32 recurrent state and the last {ck - 1}"
                            f" of {ch} channels before its convolution")
                continue
            a, b = l
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


def span_blocks(block_tables, offsets, seq_lens, span, block_size):
    """Where the ``span`` new rows of every slot go as WHOLE blocks: the
    ``n`` logical blocks from the one holding ``offsets[b]`` that a span can
    touch at any offset. Returns (ids ``[B, n]``: a block's physical id
    through the slot's table row, clamped to the table as in
    :func:`span_rows`, or the garbage block 0 where the block holds no live
    row — the only id that may repeat; shift ``[B]``: the span's first row
    within its first block; live ``[B, n * block_size]``: the rows of the
    blocks in ``[offsets[b], min(seq_lens[b], offsets[b] + span))``; edges
    ``[B, 2]``: the ids of the first block and of the last with a live row,
    the only ones that can hold rows the span must leave as they were)."""
    bt = block_tables.astype(jnp.int32)
    bs = int(block_size)
    n = -(-(span + bs - 1) // bs)
    off = offsets.astype(jnp.int32)
    end = jnp.minimum(seq_lens.astype(jnp.int32), off + jnp.int32(span))
    first = off // jnp.int32(bs)
    lb = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
    phys = jnp.take_along_axis(
        bt, jnp.minimum(lb, jnp.int32(bt.shape[1] - 1)), axis=1)
    touched = (lb * jnp.int32(bs) < end[:, None]) & (end > off)[:, None]
    ids = jnp.where(touched, phys, jnp.zeros_like(phys))
    pos = (first[:, None] * jnp.int32(bs)
           + jnp.arange(n * bs, dtype=jnp.int32)[None])
    live = (pos >= off[:, None]) & (pos < end[:, None])
    last = jnp.clip((end - 1) // jnp.int32(bs) - first, 0, n - 1)
    edges = jnp.stack(
        [ids[:, 0], jnp.take_along_axis(ids, last[:, None], axis=1)[:, 0]],
        axis=1)
    return ids, off % jnp.int32(bs), live, edges


def write_blocks(pool, rows, ids, shift, live, edges):
    """``pool`` with a span's ``rows`` ``[B, T, W]`` written as whole blocks
    at ``ids`` (:func:`span_blocks`): the rows shifted to where they lie in
    their blocks, every row of a block outside ``live`` taken from the
    pool's own first or last touched block, then ONE block scatter whose
    index count is the span's blocks, not its rows. Bit-equal to
    :func:`write_rows` on every live row and every row of a live block;
    dead blocks land whole on block 0. On a donated pool it updates the
    buffer in place (tests/test_tpu_lowering.py)."""
    B, T, W = rows.shape
    n = ids.shape[1]
    bs = pool.shape[1]
    padded = jnp.pad(rows.astype(pool.dtype),
                     ((0, 0), (bs, n * bs - T), (0, 0)))
    src = jnp.stack([jax.lax.dynamic_slice_in_dim(
        padded[b], jnp.int32(bs) - shift[b], n * bs) for b in range(B)])
    old = jnp.take(pool, edges, axis=0)  # [B, 2, bs, W]
    old = jnp.where((jnp.arange(n) == 0)[None, :, None, None],
                    old[:, :1], old[:, 1:])
    blocks = jnp.where(live.reshape(B, n, bs, 1),
                       src.reshape(B, n, bs, W), old)
    return pool.at[ids.reshape(-1)].set(blocks.reshape(B * n, bs, W))


def writes_blocks(span, block_size, window=None):
    """Does a span of ``span`` new rows a slot land in a layer's pools as
    whole blocks (:func:`write_span`'s choice, static: read from the
    shapes)? A full layer's span of at least a block a slot does. On a v5e
    (PERF.md §6) the row scatter costs ~0.2 µs a row at any batch (404 µs
    a pool for 2,048 rows, 55 as blocks), the block write a fixed ~45 µs a
    pool at 32 slots (each slot's shift and edge blocks): at 32 slots rows
    win up to 8 a slot, blocks from 16. Fewer rows (decode's one, a block
    step's or a short verify span's) keep the row scatter, and so does
    every span of a window layer's ring, whose columns repeat and of a long
    prompt the head must not land."""
    return window is None and span >= block_size


def write_span(k_pool, v_pool, k, v, block_tables, offsets, seq_lens,
               window=None):
    """Both pools with a step's new rows ``k``, ``v`` ``[B, T, H, Dh]``
    written through the block tables: where :func:`writes_blocks` says so as
    whole blocks, :func:`span_blocks` then :func:`write_blocks`; else
    :func:`span_rows` (a window layer's ring: :func:`ring_span_rows`) then
    :func:`write_rows`."""
    B, T = k.shape[0], k.shape[1]
    bs = k_pool.shape[1]
    if writes_blocks(T, bs, window):
        where = span_blocks(block_tables, offsets, seq_lens, T, bs)
        return (write_blocks(k_pool, k.reshape(B, T, -1), *where),
                write_blocks(v_pool, v.reshape(B, T, -1), *where))
    if window is None:
        blk, row = span_rows(block_tables, offsets, seq_lens, T, bs)
    else:
        blk, row = ring_span_rows(block_tables, offsets, seq_lens, T, bs,
                                  window)
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
