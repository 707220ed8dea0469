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

__all__ = ["grouped_matmul", "items"]

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
