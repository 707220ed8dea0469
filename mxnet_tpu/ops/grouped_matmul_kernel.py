"""A routed FFN's segment matmul as ONE Pallas TPU kernel: each expert
multiplies its own rows, by tiles of the rows there are.

``rows [M, K]`` lie sorted by expert, expert `e`'s ``sizes[e]`` of them
after expert ``e - 1``'s, and ``w [E, K, N]`` holds the experts' matrices:
the result is ``rows[seg_e] @ w[e]`` a segment, ``[M, N]`` — what
``lax.ragged_dot(rows, w, sizes)`` computes.  XLA's form of it on the TPU
walks the rows by a 512-row tile and multiplies a whole tile for every
expert whose segment touches it (``parallel/moe.py``); at a few hundred
rows an expert that is two to three times the rows there are.  Here:

* the rows are walked by a tile of `tm` (128), and a tile that holds the
  edge of two segments is visited once a segment, the rows of the other
  masked as they are stored (the scheme of
  ``jax.experimental.pallas.ops.tpu.megablox``): the ITEMS of the walk,
  ``(expert, row tile)`` in order, are made from `sizes` before the call
  and scalar-prefetched — at most ``ceil(M / tm) + E - 1`` of them, the
  grid's length; the ones a call does not fill do nothing.  `M` is taken
  as it is: the last tile may be partial;
* an expert's matrix is read from HBM ONCE a call and only if the expert
  has a row: `w` stays in HBM and the kernel copies matrix ``e`` — `K`
  whole, `tn` of its `N` columns (all of them where they fit: `tn` is
  ``parallel.moe.kernel_tiles``') — into one of two VMEM slots while the
  expert before it multiplies (the first item of an expert waits for its
  own copy and starts the next expert's), then rounds it to bfloat16 into
  a third buffer that all the expert's row tiles multiply by.  Where `N`
  is walked in strips the rows are read once a strip;
* both operands are rounded to bfloat16 in VMEM and the products are
  accumulated in float32 — the one bfloat16 pass that XLA's default
  precision gives a float32 dot on a TPU.  Nothing is stored smaller.

Rows past the last segment (pairs of experts held elsewhere, the tail of
a held range's pass) belong to no item: inside a tile that also holds a
segment's rows they are written as ZEROS, a tile that holds none of a
segment is NOT WRITTEN at all (whatever the buffer held).  Both callers
(``parallel/moe.py _dropless`` / ``_held_passes``) set such rows to 0 by a
select.  A row's result depends on that row alone, so whatever such rows
hold as INPUT reaches no other row.

Measured on a TPU v5e (PERF.md section 6, PR 60).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "gate_up", "down", "slab_sum", "items"]

_BF16 = jnp.bfloat16


def items(sizes, m, tm):
    """The walk of `m` sorted rows by tiles of `tm`, from the experts'
    `sizes [E]`: ``(expert, tile, turn, following, offsets, count)`` —
    item i is row tile ``tile[i]`` of expert ``expert[i]``, ``turn[i]``
    says how many experts with rows came before it (the slot its matrix
    lies in is its parity) and ``following[i]`` which expert's items come
    after its expert's (-1: none), an expert's rows are ``offsets[e] ..
    offsets[e + 1]`` and `count` items are real; the ones after them
    repeat the last (so that no block moves for them)."""
    n_exp = sizes.shape[0]
    length = pl.cdiv(m, tm) + n_exp - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = jnp.cumsum(tiles)
    count = item_ends[-1]
    i = jnp.clip(jnp.arange(length, dtype=jnp.int32), 0,
                 jnp.maximum(count - 1, 0))
    # (every item against every expert's last: no loop in the program)
    expert = jnp.minimum(
        jnp.searchsorted(item_ends, i, side="right",
                         method="compare_all").astype(jnp.int32),
        n_exp - 1)
    tile = first[expert] + i - (item_ends[expert] - tiles[expert])
    turn = jnp.cumsum(sizes > 0)[expert] - 1
    after = item_ends[expert]
    following = jnp.where(after < count,
                          expert[jnp.minimum(after, length - 1)], -1)
    offsets = jnp.concatenate([starts[:1], ends])
    return (expert, jnp.clip(tile, 0, pl.cdiv(m, tm) - 1).astype(jnp.int32),
            jnp.maximum(turn, 0).astype(jnp.int32), following, offsets,
            count[None])


def _kernel(expert_ref, tile_ref, turn_ref, following_ref, offsets_ref,
            count_ref, x_ref, w_hbm, o_ref, slots, rounded, sems, *, tm, tn):
    strip, i = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    before = jnp.maximum(i - 1, 0)
    e, turn = expert_ref[i], turn_ref[i]
    opens = (i == 0) | (expert_ref[before] != e)      # e's first row tile
    fresh = (i == 0) | (tile_ref[before] != tile_ref[i])

    def copy(expert, slot):
        return pltpu.make_async_copy(
            w_hbm.at[expert, :, pl.ds(strip * tn, tn)], slots.at[slot],
            sems.at[slot])

    @pl.when((i < count) & opens)
    def _():
        slot = turn % 2

        @pl.when(i == 0)
        def _():
            copy(e, slot).start()

        copy(e, slot).wait()
        following = following_ref[i]

        @pl.when(following >= 0)
        def _():
            copy(following, 1 - slot).start()

        rounded[...] = slots[slot].astype(_BF16)

    @pl.when(i < count)
    def _():
        y = jnp.dot(x_ref[...].astype(_BF16), rounded[...],
                    preferred_element_type=jnp.float32)
        row = tile_ref[i] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        kept = jnp.where(fresh, 0, o_ref[...].astype(jnp.float32))
        o_ref[...] = jnp.where(mine, y, kept).astype(o_ref.dtype)


def grouped_matmul(rows, w, sizes, *, tm, tn, interpret=False):
    """``rows [M, K]`` sorted by expert, ``w [E, K, N]``, ``sizes [E]``
    int32 (their sum at most `M`) → ``[out [M, N]]`` of `rows`' dtype: each
    expert's segment times its matrix, one bfloat16 pass accumulated in
    float32; rows past the last segment zeros or untouched (the module
    text says which).  `tm` rows and `tn` columns a tile
    (``parallel.moe.kernel_tiles``, which also says for which shapes the
    kernel's tiling holds); `interpret` runs Pallas's interpreter.  The
    caller jits or exports (``ops/exported.py``)."""
    m, k = rows.shape
    n_exp, _, n = w.shape
    walk = items(sizes, m, tm)
    at_tile = lambda strip, i, expert, tile, *_: (tile[i], strip)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // tn, walk[0].shape[0]),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda strip, i, expert, tile, *_:
                                   (tile[i], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((tm, tn), at_tile)],
            scratch_shapes=[pltpu.VMEM((2, k, tn), w.dtype),
                            pltpu.VMEM((k, tn), _BF16),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((m, n), rows.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(tm, tn, k, w.dtype.itemsize)),
        # every matrix once a strip — fewer where experts have no row —,
        # the rows once a strip, the result once
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(w.size * w.dtype.itemsize
                            + (n // tn * m * k + m * n)
                            * rows.dtype.itemsize)),
        name="grouped_matmul_kernel",
        interpret=interpret,
    )(*walk, rows, w)


def _vmem(tm, tn, k, itemsize):
    """The VMEM the call asks for: the matrix's two slots and its rounded
    copy, the pipeline's two row tiles and two result tiles, the rounded
    row tile and the products, and as much again as the row tile for what
    the compiler stages."""
    return (2 * k * tn * (itemsize + 1) + tm * k * (2 * 4 + 2 + 4)
            + 3 * tm * tn * 4 + (4 << 20))


# (The two calls and their account stand BELOW the first kernel, whose
# lines keep their numbers: a Mosaic kernel's body holds its source
# locations, and a program that calls `grouped_matmul` alone — every held
# range's — stays the parent's byte for byte, compile cache and all.)
#
# Where every pair's row is walked and no expert has a bias
# (``parallel/moe.py _dropless`` says where), the layer's three products are
# TWO calls that also fetch and place their own rows (PR 61), so that no ``[T
# k, D]`` copy of `x` is made before them and no un-sort, reshape or second
# pass after them:
#
# * `gate_up` leaves `x` in HBM and reads ``token [M]`` — the sorted pairs'
#   tokens — from SMEM: a row tile's `tm` rows arrive by ROW DMA, one a
#   row, into one of two VMEM buffers, the next tile's while this one
#   multiplies, and are rounded to bfloat16 once a tile.  An expert's
#   ``w1`` and ``w3`` are copied and rounded together, and ``h = act(x w1) *
#   (x w3)`` leaves as ``[M, H]`` bfloat16: `g` and `u` never leave VMEM, and
#   `h` is stored as what `down` rounds it to first thing (the same bits
#   either way);
# * `down` multiplies a tile of `h` by the expert's ``w2``, scales each row
#   by its router weight in float32 on the vector unit, and sends row `r` to
#   ``out[dest[r]]`` by row DMA — only a segment's own rows of a tile that
#   two segments share.  The caller's ``dest = slot * T + token`` makes `out`
#   k slabs of ``[T, D]`` whose sum over k is the layer's result
#   (`slab_sum`, a third, small call).  Every row of `out` whose `dest`
#   names it is written once; no other row is written.
#
# Mosaic slices no ONE row out of an array tiled ``(8, 128)``, in HBM or in
# VMEM: a row that a DMA moves is a row of ``[T, 1, D]`` (XLA lays it out
# ``T(1,128)``: a row's `D` values lie one after the other) and of a ``(tm,
# 1, D)`` buffer, which the kernel reads and writes as ``buf[:, 0, :]``.  So
# `gate_up` takes `x` as ``[T, 1, D]`` and `down` returns ``[rows, 1, D]``:
# the relayout in and out is XLA's, once a layer, where it can.
#
# Both walk the same items as `grouped_matmul` and hold an expert's matrices
# whole (no strips: ``parallel.moe.fused_tile`` leaves a layer whose matrices
# do not fit to the three calls).
#
# Measured on a TPU v5e (PERF.md section 6, PR 61).


def _expert_opens(i, count, e, turn, expert_ref, following_ref, copies):
    """Item `i`'s part of the matrices' relay, where it is the first of
    expert `e`: wait for `e`'s copies (the first item of a call starts
    them itself) and start the following expert's into the other slot.
    ``copies(expert, slot)`` lists the copies of one expert's matrices."""
    before = jnp.maximum(i - 1, 0)
    opens = (i == 0) | (expert_ref[before] != e)

    def relay(then):
        @pl.when((i < count) & opens)
        def _():
            slot = turn % 2

            @pl.when(i == 0)
            def _():
                for copy in copies(e, slot):
                    copy.start()

            for copy in copies(e, slot):
                copy.wait()
            following = following_ref[i]

            @pl.when(following >= 0)
            def _():
                for copy in copies(following, 1 - slot):
                    copy.start()

            then(slot)
    return relay


def _gate_up_kernel(expert_ref, tile_ref, turn_ref, following_ref,
                    offsets_ref, count_ref, token_ref, x_hbm, *refs, tm, act):
    *w_hbm, o_ref, rows, tile_rows, slots, rounded, row_sems, w_sems = refs
    i, (m,) = pl.program_id(0), token_ref.shape
    count = count_ref[0]
    e, turn, tile = expert_ref[i], turn_ref[i], tile_ref[i]
    fresh = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile)
    # the tiles that hold a segment's row lie one after the other from 0
    tiles = pl.cdiv(offsets_ref[offsets_ref.shape[0] - 1], tm)

    def fetch(tile):
        """Start the copies of `tile`'s rows of `x`: a row a DMA (a row
        past the last names the last: a copy more of a row that is there)."""
        slot = tile % 2

        def eight(r8, carry):
            for r in range(8):           # (Mosaic unrolls whole or not)
                r = r8 * 8 + r
                at = token_ref[jnp.minimum(tile * tm + r, m - 1)]
                pltpu.make_async_copy(x_hbm.at[at], rows.at[slot, r],
                                      row_sems.at[slot]).start()
            return carry
        lax.fori_loop(0, tm // 8, eight, 0)

    @pl.when((i == 0) & (count > 0))
    def _():
        fetch(tile)

    def copies(expert, slot):
        return [pltpu.make_async_copy(w.at[expert], slots.at[slot, n],
                                      w_sems.at[slot, n])
                for n, w in enumerate(w_hbm)]

    @_expert_opens(i, count, e, turn, expert_ref, following_ref, copies)
    def _(slot):
        for n in range(len(w_hbm)):
            rounded[n] = slots[slot, n].astype(_BF16)

    @pl.when((i < count) & fresh)
    def _():
        slot = tile % 2
        # the tile's `tm` copies signalled one semaphore: one wait for
        # their bytes together (the wait needs the shapes only)
        pltpu.make_async_copy(rows.at[slot], rows.at[slot],
                              row_sems.at[slot]).wait()
        # rounded, and from a sublane a row to whole tiles, once a tile:
        # two segments that share it read the same copy
        tile_rows[...] = rows[slot, :, 0, :].astype(_BF16)

        @pl.when(tile + 1 < tiles)
        def _():
            fetch(tile + 1)

    @pl.when(i < count)
    def _():
        x = tile_rows[...]
        from ..parallel.moe import ACTIVATIONS

        h = ACTIVATIONS[act](
            jnp.dot(x, rounded[0], preferred_element_type=jnp.float32))
        if len(w_hbm) == 2:
            h = h * jnp.dot(x, rounded[1],
                            preferred_element_type=jnp.float32)
        row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        kept = jnp.where(fresh, 0, o_ref[...].astype(jnp.float32))
        o_ref[...] = jnp.where(mine, h, kept).astype(o_ref.dtype)


def gate_up(x, token, sizes, w1, w3=None, *, tm, act, interpret=False):
    """``x [T, D]``, ``token [M]`` int32 — sorted row r is ``x[token[r]]``
    —, ``sizes [E]`` (their sum at most `M`), ``w1`` and ``w3 [E, D, H]``
    (no `w3`: an un-gated FFN) → ``[h [M, H]]`` bfloat16: each expert's
    segment as ``act(rows w1) * (rows w3)``, one bfloat16 pass each
    accumulated in float32, the product in float32, rounded once as it is
    stored.  `act` names one of ``parallel.moe.ACTIVATIONS``; `tm` rows
    a tile (``parallel.moe.fused_tile``).  Rows past the last segment as
    `grouped_matmul` leaves them."""
    m, = token.shape
    matrices = [w1] if w3 is None else [w1, w3]
    _, k, n = w1.shape
    walk = items(sizes, m, tm)
    itemsize = w1.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_gate_up_kernel, tm=tm, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(walk[0].shape[0],),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(matrices)),
            out_specs=[pl.BlockSpec((tm, n),
                                    lambda i, expert, tile, *_: (tile[i], 0))],
            scratch_shapes=[pltpu.VMEM((2, tm, 1, k), x.dtype),
                            pltpu.VMEM((tm, k), _BF16),
                            pltpu.VMEM((2, len(matrices), k, n), w1.dtype),
                            pltpu.VMEM((len(matrices), k, n), _BF16),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2, len(matrices)))]),
        out_shape=[jax.ShapeDtypeStruct((m, n), _BF16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the matrices' two slots and their rounded copies, the rows'
            # two buffers and their rounded copy, the products, the
            # result's two tiles, and what the compiler stages
            vmem_limit_bytes=(len(matrices) * k * n * (2 * itemsize + 2)
                              + tm * k * (2 * x.dtype.itemsize + 2)
                              + tm * n * (3 * 4 + 2 * 2) + (4 << 20))),
        # every matrix once — fewer where experts have no row —, a row of
        # `x` a pair, the result once
        cost_estimate=pl.CostEstimate(
            flops=2 * len(matrices) * m * k * n,
            transcendentals=0 if act.startswith("relu") else m * n,
            bytes_accessed=(len(matrices) * w1.size * itemsize
                            + m * k * x.dtype.itemsize + m * n * 2)),
        name="grouped_gate_up_kernel",
        interpret=interpret,
    )(*walk, token.astype(jnp.int32), x.reshape(-1, 1, k), *matrices)


def _down_kernel(expert_ref, tile_ref, turn_ref, following_ref, offsets_ref,
                 count_ref, dest_ref, h_ref, weight_ref, w_hbm, out_hbm,
                 slots, rounded, sent, w_sems, row_sems, *, tm):
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    count = count_ref[0]
    e, turn = expert_ref[i], turn_ref[i]

    def rows_of(item, wait):
        """Item `item`'s own rows of its tile, each from the item's buffer
        to its place in `out`: start the copies, or wait for them."""
        expert, first = expert_ref[item], tile_ref[item] * tm
        slot = item % 2

        lo = jnp.maximum(first, offsets_ref[expert])
        hi = jnp.minimum(first + tm, offsets_ref[expert + 1])

        def row(r, carry):
            copy = pltpu.make_async_copy(
                sent.at[slot, 0 if wait else r - first],
                out_hbm.at[0 if wait else dest_ref[r]], row_sems.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        if wait:
            # a whole tile's copies signalled one semaphore `tm` rows'
            # bytes: one wait for them together
            @pl.when(hi - lo == tm)
            def _():
                pltpu.make_async_copy(sent.at[slot], sent.at[slot],
                                      row_sems.at[slot]).wait()
            lo = jnp.where(hi - lo == tm, hi, lo)
        lax.fori_loop(lo, hi, row, 0)

    # the buffer this item fills was sent from two items ago
    @pl.when((i >= 2) & (i - 2 < count))
    def _():
        rows_of(i - 2, wait=True)

    def copies(expert, slot):
        return [pltpu.make_async_copy(w_hbm.at[expert], slots.at[slot],
                                      w_sems.at[slot])]

    @_expert_opens(i, count, e, turn, expert_ref, following_ref, copies)
    def _(slot):
        rounded[...] = slots[slot].astype(_BF16)

    @pl.when(i < count)
    def _():
        y = jnp.dot(h_ref[...].astype(_BF16), rounded[...],
                    preferred_element_type=jnp.float32)
        # the tile's weights lie along the lanes: each to its row's
        # sublane by a masked sum of one term (exact), and the rows scaled
        # in float32 on the vector unit — a matmul would round the scores
        # to bfloat16
        across = lax.broadcasted_iota(jnp.int32, (tm, tm), 1)
        down_ = lax.broadcasted_iota(jnp.int32, (tm, tm), 0)
        weight = jnp.sum(jnp.where(across == down_, weight_ref[0], 0.0),
                         axis=1, keepdims=True)
        sent[i % 2, :, 0, :] = (y * weight).astype(sent.dtype)
        rows_of(i, wait=False)

    @pl.when(i == last)
    def _():
        for item in (i - 1, i):
            @pl.when((item >= 0) & (item < count))
            def _():
                rows_of(item, wait=True)


def down(h, w, sizes, dest, weight, *, tm, dtype, interpret=False):
    """``h [M, H]`` sorted by expert, ``w [E, H, D]``, ``sizes [E]``,
    ``dest [M]`` int32 (distinct, below `M`) and ``weight [M]`` float32 →
    ``[out [M, 1, D]]`` of `dtype` (a name): ``out[dest[r]] = (h[r] @
    w[e]) * weight[r]`` for every row r of a segment — one bfloat16 pass
    accumulated in float32, the scaling in float32 —, sent there by row
    DMA; a row of `out` that no `dest` names is not written."""
    m, k = h.shape
    n = w.shape[2]
    dtype = jnp.dtype(dtype)
    walk = items(sizes, m, tm)
    tiles = pl.cdiv(m, tm)
    weight = jnp.pad(weight.astype(jnp.float32),
                     (0, tiles * tm - m)).reshape(tiles, 1, tm)
    at_tile = lambda i, expert, tile, *_: (tile[i], 0)
    return pl.pallas_call(
        functools.partial(_down_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(walk[0].shape[0],),
            in_specs=[pl.BlockSpec((tm, k), at_tile),
                      pl.BlockSpec((1, 1, tm),
                                   lambda i, expert, tile, *_:
                                   (tile[i], 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((2, k, n), w.dtype),
                            pltpu.VMEM((k, n), _BF16),
                            pltpu.VMEM((2, tm, 1, n), dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((m, 1, n), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the matrix's two slots and its rounded copy, the pipeline's
            # two tiles of `h`, the products, the two buffers sent from,
            # and what the compiler stages
            vmem_limit_bytes=(k * n * (2 * w.dtype.itemsize + 2)
                              + 2 * tm * k * h.dtype.itemsize
                              + tm * n * (2 * 4 + 2 * dtype.itemsize)
                              + (4 << 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(w.size * w.dtype.itemsize
                            + m * k * h.dtype.itemsize
                            + m * n * dtype.itemsize)),
        name="grouped_down_kernel",
        interpret=interpret,
    )(*walk, dest.astype(jnp.int32), h, weight, w)


def _slab_sum_kernel(s_ref, o_ref):
    total = s_ref[0, :, 0, :]
    for slot in range(1, s_ref.shape[0]):
        total = total + s_ref[slot, :, 0, :]
    o_ref[...] = total


def slab_sum(slabs, *, k, tb=128, interpret=False):
    """`down`'s ``slabs [k T, 1, D]`` → ``[out [T, D]]``: the k slabs added,
    slot 0 first, `tb` tokens a step, and the sum stored as whole ``(8,
    128)`` tiles.  XLA's own sum of the slabs keeps the rows of one
    sublane, and the loop over a long bucket's pieces then stacks its
    results in that layout: 1.2 ms a piece of SmallThinker's for the
    update alone (PERF.md section 6, PR 61)."""
    rows, _, d = slabs.shape
    t = rows // k
    itemsize = slabs.dtype.itemsize
    return pl.pallas_call(
        _slab_sum_kernel,
        grid=(pl.cdiv(t, tb),),
        in_specs=[pl.BlockSpec((k, tb, 1, d), lambda i: (0, i, 0, 0))],
        out_specs=[pl.BlockSpec((tb, d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, d), slabs.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * (k + 2) * tb * d * itemsize + (4 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=(k - 1) * t * d, transcendentals=0,
            bytes_accessed=(k + 1) * t * d * itemsize),
        name="grouped_slab_sum_kernel",
        interpret=interpret,
    )(slabs.reshape(k, t, 1, d))
