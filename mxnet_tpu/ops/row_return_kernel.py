"""A held range's pass returns its rows to their tokens as ONE Pallas TPU
kernel: the token order's result is walked by tiles of its tokens, each
tile read once and written once, and a tile's rows arrive by row DMA.

``ys [rows, D]`` are a pass's weighted results, sorted by expert; row r is
token ``token[r]``'s, the first `count` of them are live and the others
nobody's, and ``out [T, D]`` holds what earlier passes left: the result is
`out` with every live row added to its token's row — what
``out.at[token].add(ys, mode="drop")`` computes where the dead rows' tokens
say `T`.  XLA's form of it on the TPU is one read-modify-write a row whose
price goes by the row's width and nothing else (4.5 us a row of 5,120
floats: PERF.md section 7, PR 57).  Here:

* a token may own SEVERAL live rows of a pass (two held experts), so no
  row is added where it lies: the pass's rows are sorted BY TOKEN before
  the call (a stable sort of `rows` keys — a token's rows stay in
  ascending expert), and the walk is over ``(token tile, row chunk)``
  items — the items of ``ops.grouped_matmul_kernel.items`` with a tile of
  `tb` tokens in an expert's place and its live rows as the segment —,
  scalar-prefetched.  A token's sum is written once a pass: its tile is
  in VMEM while all its rows are added, ascending expert, onto what `out`
  held;
* `out` comes and goes by a plain ``(tb, D)`` block, aliased input to
  output: a tile that no live row names gets no item and is not touched,
  and no pass copies the result;
* `ys` stays in HBM as ``[rows, 1, D]`` (the layout a row DMA can slice:
  ``ops/grouped_matmul_kernel.py``, `gate_up`): a chunk's `tm` rows arrive
  by row DMA — a row a copy, named by the sort's permutation — into one of
  two VMEM buffers, the next chunk's while this one is added.  Every chunk
  that holds a live row is fetched once, whole (`tm` copies on one
  semaphore), and waited for once, whole, by the first item that reads it:
  no copy is started that is not waited for in the same call, and no wait
  stands without its `tm` copies.  A chunk's rows past `count` are copies
  of rows that are there (zeros) and are added nowhere.

float32 rows added in float32 on the vector unit.

Measured on a TPU v5e (PERF.md section 6, PR 63).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul_kernel import items

__all__ = ["row_return"]


def _kernel(tile_ref, chunk_ref, offsets_ref, count_ref, source_ref,
            token_ref, in_ref, ys_hbm, o_ref, rows, chunk_rows, sems, *,
            tb, tm):
    i, (m,) = pl.program_id(0), source_ref.shape
    count = count_ref[0]
    tile, chunk = tile_ref[i], chunk_ref[i]
    before = jnp.maximum(i - 1, 0)
    opens = (i == 0) | (tile_ref[before] != tile)     # the tile's first item
    fresh = (i == 0) | (chunk_ref[before] != chunk)
    # the chunks that hold a live row lie one after the other from 0
    chunks = pl.cdiv(offsets_ref[offsets_ref.shape[0] - 1], tm)

    def fetch(chunk):
        """Start the copies of `chunk`'s rows of `ys`: a row a DMA (a row
        past the last names the last: a copy more of a row that is
        there)."""
        slot = chunk % 2

        def eight(r8, carry):
            for r in range(8):           # (Mosaic unrolls whole or not)
                r = r8 * 8 + r
                at = source_ref[jnp.minimum(chunk * tm + r, m - 1)]
                pltpu.make_async_copy(ys_hbm.at[at], rows.at[slot, r],
                                      sems.at[slot]).start()
            return carry
        lax.fori_loop(0, tm // 8, eight, 0)

    @pl.when(opens)
    def _():
        # (also where a call holds no item: block 0 goes back as it came)
        o_ref[...] = in_ref[...]

    @pl.when((i == 0) & (count > 0))
    def _():
        fetch(chunk)

    @pl.when((i < count) & fresh)
    def _():
        slot = chunk % 2
        # the chunk's `tm` copies signalled one semaphore: one wait for
        # their bytes together (the wait needs the shapes only)
        pltpu.make_async_copy(rows.at[slot], rows.at[slot],
                              sems.at[slot]).wait()
        # from a sublane a row to whole tiles, once a chunk: two token
        # tiles that share it read the same copy
        chunk_rows[...] = rows[slot, :, 0, :]

        @pl.when(chunk + 1 < chunks)
        def _():
            fetch(chunk + 1)

    @pl.when(i < count)
    def _():
        lo = jnp.maximum(chunk * tm, offsets_ref[tile])
        hi = jnp.minimum(chunk * tm + tm, offsets_ref[tile + 1])

        def add(r, carry):
            at = pl.ds(token_ref[r] - tile * tb, 1)
            o_ref[at, :] = o_ref[at, :] + chunk_rows[pl.ds(r - chunk * tm, 1),  # mxlint: disable=E006 -- a Pallas Ref: the store is the kernel's write to VMEM, staged into the loop body
                                                     :]
            return carry
        lax.fori_loop(lo, hi, add, 0)


def row_return(out, ys, token, count, *, tb, tm, interpret=False):
    """``out [T, D]``, ``ys [rows, D]`` of its dtype, ``token [rows]`` int32
    — row r is token ``token[r]``'s — and ``count`` (a scalar: the rows
    from `count` on are nobody's, whatever their `token`) → ``[out [T,
    D]]`` with every live row added to its token's row, a token's rows in
    the order they lie in `ys`, after what `out` held; `out` is aliased to
    the result.  `tb` tokens a tile and `tm` rows a chunk
    (``parallel.moe.return_tiles``, which also says for which shapes the
    kernel's tiling holds); `interpret` runs Pallas's interpreter.  The
    caller jits or exports (``ops/exported.py``)."""
    t_len, d = out.shape
    m, = token.shape
    tiles = pl.cdiv(t_len, tb)
    live = jnp.arange(m, dtype=jnp.int32) < count
    # the live rows by token (stable: a token's rows stay as they lay),
    # the others behind them
    by_token, source = lax.sort(
        (jnp.where(live, token.astype(jnp.int32), t_len),
         jnp.arange(m, dtype=jnp.int32)), num_keys=1, is_stable=True)
    # (every tile's end against every row: no loop in the program)
    ends = jnp.searchsorted(
        by_token, jnp.arange(1, tiles + 1, dtype=jnp.int32) * tb,
        side="left", method="compare_all").astype(jnp.int32)
    tile, chunk, _, _, offsets, n_items = items(
        jnp.diff(ends, prepend=0), m, tm)
    itemsize = out.dtype.itemsize
    at_tile = lambda i, tile, *_: (tile[i], 0)
    return pl.pallas_call(
        functools.partial(_kernel, tb=tb, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(tile.shape[0],),
            in_specs=[pl.BlockSpec((tb, d), at_tile),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((tb, d), at_tile)],
            scratch_shapes=[pltpu.VMEM((2, tm, 1, d), ys.dtype),
                            pltpu.VMEM((tm, d), ys.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(out.shape, out.dtype)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the pipeline's two tiles in and two out, the rows' two
            # buffers and their re-tiled copy, and what the compiler
            # stages
            vmem_limit_bytes=((4 * tb + 3 * tm) * d * itemsize + (4 << 20))),
        # a live row read once; a tile that has one read and written once
        cost_estimate=pl.CostEstimate(
            flops=m * d, transcendentals=0,
            bytes_accessed=(m + 2 * min(m, tiles) * tb) * d * itemsize),
        name="row_return_kernel",
        interpret=interpret,
    )(tile, chunk, offsets, n_items, source, by_token, out,
      ys.reshape(m, 1, d))
