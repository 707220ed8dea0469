"""The decode step's attention against the KV ring as ONE Pallas TPU
kernel a layer: the step's K/V rows go into the donated rings in place
and each packed row reads its page only as far as it is filled.

The ring is stored ``(slots, H_kv, d_head, max_len)`` — positions on the
lanes (``TransformerLM.cache_spec`` owns the order) — which is the layout
the kernel's operands have by default, so nothing is copied or padded on
the way in.  A grid step ``(b, j, i)`` holds block `i` (`block` positions:
``ops.attention.decode_block``) of head group `j` (`heads` K/V heads:
``ops.attention.decode_heads`` — all of them, ONE group, wherever 128
positions of all heads fit a block; heads are independent in a decode
step, so a wider ring is cut into groups of whole heads) of row `b`'s
page in VMEM, brought there by the pipeline from ``(slot[b], j * heads, :,
i * block)``; slot and length are scalar-prefetched, and for `i` beyond the block that holds position
``length[b]`` the index map repeats that block, which the pipeline does
not fetch again, and the body does nothing: the blocks beyond are
SKIPPED, not masked.

**A ring that wraps** (`wraps`: a window layer's, W positions for a
session that may grow past them; ``TransformerLM.cache_spec`` gives each
ring its own length and this one kernel serves both).  The step's row
goes to position ``length mod W``, so the block that is WRITTEN, ``(length
mod W) // block``, is no longer the last one READ, ``min(length // block,
W / block - 1)``: once ``length >= W`` every block is read, the mask
``position <= length`` keeps all of it, and what the ring then holds is
exactly the window, the new row having taken the place of the one that
just left it.  The row is put in and sent back in the grid step that
holds its block and the DMA is awaited at the end of that same step;
the output is finished in the last block's.  Without `wraps` the two
blocks are one and the program is the one it was.

Positions on the lanes make the two reductions cheap on the vector unit —
scores reduce over d_head on the sublanes, the context accumulates over
positions elementwise and is lane-reduced once a row — and make the new
row one lane of every (head, d_head) line.  It is written where the
kernel already is: the block that holds ``length[b]`` is the last one the
row reads, so the step's K/V are put into that block where it lies in
VMEM (one masked store a vector), the row attends to it — write, then
read: a token attends to itself — and the 128 positions around the new
one go back to their place in the ring by ONE dense DMA a ring, which
runs under the arithmetic.  Scores, softmax (online over the blocks) and
context are float32 multiply-adds on the vector unit, as the
``jax.numpy`` body (``ops.attention._ring_attention``, the oracle)
compiles them; no matrix-unit pass rounds an operand to bfloat16.

Measured on a TPU v5e (PERF.md section 6, PR 32): 12 ring layers, 8 rows
of 32 heads x 64, mean 216 of 768 positions filled, 1.14 ms a step
against 3.34 for the ``jax.numpy`` body; what was tried and was slower is
there too (whole-array broadcasts and transposes for the columns, loops
left rolled).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ring_attention"]

_LANE = 128
_NEG = -1e30          # ops/attention.py's mask value: finite

def _kernel(slot_ref, len_ref,                       # scalar prefetch
            q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, ko_hbm, vo_hbm,
            qb_ref, s_ref, m_ref, l_ref, a_ref, acc_ref, sem,
            *, h_kv, groups, d_head, blk, nblk, scale, wraps):
    # h_kv: the K/V heads of THIS grid step's head group
    b, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h_q = h_kv * groups
    chunks = blk // _LANE
    # a tile of 128 (head, d_head) lines holds `per` heads, or a head
    # lies over `tiles` of them
    per, tiles = max(_LANE // d_head, 1), max(d_head // _LANE, 1)
    length = len_ref[b]
    # where the step's row goes, the block that holds it, the last block
    # the row reads: one block unless the ring wraps
    at = length % (nblk * blk) if wraps else length
    put = at // blk
    last = jnp.minimum(length // blk, nblk - 1) if wraps else put

    def column(heads_ref, h):
        """Head `h` of a row's ``(1, 1, d_head, H)`` operand, each value
        broadcast along the lanes of its own d_head line."""
        return jnp.broadcast_to(heads_ref[0, 0, :, pl.ds(h, 1)],
                                (d_head, _LANE))

    def unrolled(n, body):
        """`body(index)` for every index below `n`: traced once, lowered
        `n` times with the index a constant, so each copy's slices are
        static and the copies overlap."""
        lax.fori_loop(0, n, lambda index, carry: body(index) or carry, 0,
                      unroll=True)

    @pl.when(i == 0)
    def _start_row():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def query(h):
            qb_ref[h] = column(q_ref, h)
        unrolled(h_q, query)

    @pl.when(i == put)
    def _write_row():
        # the step's row is ONE LANE of this block: put it in where the
        # block lies in VMEM (a masked store a vector) and send the 128
        # positions around it back to the ring; the DMAs run under the
        # arithmetic below and are awaited at the end of this grid step,
        # before the pipeline may fetch into this buffer again
        off = at % blk
        chunk = pl.ds(pl.multiple_of(off // _LANE * _LANE, _LANE), _LANE)
        lane = lax.broadcasted_iota(jnp.int32, (d_head, _LANE), 1) \
            == off % _LANE

        def row(g):
            for new, block in ((kn_ref, k_ref), (vn_ref, v_ref)):
                pltpu.store(block.at[0, g, :, chunk],
                            column(new, g).astype(block.dtype), mask=lane)
        unrolled(h_kv, row)
        for n, (block, ring) in enumerate(((k_ref, ko_hbm), (v_ref, vo_hbm))):
            pltpu.make_async_copy(
                block.at[0, :, :, chunk],
                ring.at[slot_ref[b], pl.ds(j * h_kv, h_kv), :,
                        pl.ds(pl.multiple_of(at // _LANE * _LANE, _LANE),
                              _LANE)],
                sem.at[n]).start()

    @pl.when(i <= last)
    def _attend():
        def scores(g, carry):
            for c in range(chunks):
                kh = k_ref[0, g, :, c * _LANE:(c + 1) * _LANE]
                for r in range(groups):
                    h = g * groups + r
                    # mxlint: disable=E006 -- a Pallas Ref: the store is the kernel's write to VMEM, staged into the loop body
                    s_ref.at[c][pl.ds(h, 1), :] = jnp.sum(
                        qb_ref[h] * kh, axis=0, keepdims=True)
            return carry
        lax.fori_loop(0, h_kv, scores, 0)
        lane = lax.broadcasted_iota(jnp.int32, (h_q, _LANE), 1)
        s = []
        for c in range(chunks):           # each (H_q, 128)
            sc = s_ref[c]
            sc = (sc / jnp.sqrt(jnp.float32(d_head)) if scale is None
                  else sc * scale)
            s.append(jnp.where(i * blk + c * _LANE + lane <= length,
                               sc, _NEG))
        m_prev = m_ref[...]
        m_new = m_prev
        for sc in s:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...]
        for c, sc in enumerate(s):
            p = jnp.exp(sc - m_new)
            s_ref[c] = p
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = l_new
        m_ref[...] = m_new
        a_ref[...] = alpha

        def context(g, carry):
            for r in range(groups):
                h = g * groups + r
                acc = acc_ref[h] * a_ref[pl.ds(h, 1), :]
                for c in range(chunks):
                    acc = acc + (s_ref.at[c][pl.ds(h, 1), :]
                                 * v_ref[0, g, :, c * _LANE:(c + 1) * _LANE])
                # mxlint: disable=E006 -- a Pallas Ref, as above
                acc_ref[h] = acc
            return carry
        lax.fori_loop(0, h_kv, context, 0)

    def row_sent():
        for n, ring in enumerate((ko_hbm, vo_hbm)):
            pltpu.make_async_copy(  # the wait needs the shapes only
                k_ref.at[0, :, :, pl.ds(0, _LANE)],
                ring.at[0, pl.ds(0, h_kv), :, pl.ds(0, _LANE)],
                sem.at[n]).wait()

    @pl.when(i == last)
    def _finish_row():
        # context[h, j] = sum over the lanes of acc[h, j, :] / l[h], 128
        # (head, d) lines at a time: turned, the lanes' sum is a sum of
        # vectors and the results lie side by side, as the output does
        lane = lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

        def tile(t):
            norm = l_ref[pl.ds(t * per, 1), :]
            for k in range(1, per):
                norm = jnp.where(lane < k * d_head, norm,
                                 l_ref[pl.ds(t * per + k, 1), :])
            lines = acc_ref[pl.ds(t * per, per)].reshape(_LANE, _LANE)
            o_ref[0, :, pl.ds(pl.multiple_of(t * _LANE, _LANE), _LANE)] = (
                jnp.sum(lines.T, axis=0, keepdims=True) / norm)

        def wide_tile(t):
            # a head of 256 or more lies over `tiles` tiles: tile t is
            # lines (t % tiles) * 128 .. of head t // tiles
            h, part = t // tiles, t % tiles
            lines = acc_ref[h, part * _LANE:(part + 1) * _LANE, :]
            o_ref[0, :, t * _LANE:(t + 1) * _LANE] = (
                jnp.sum(lines.T, axis=0, keepdims=True) / l_ref[h:h + 1, :])

        if tiles == 1:
            unrolled(h_q // per, tile)
        else:
            for t in range(h_q * tiles):
                wide_tile(t)
        if not wraps:     # the block that was written: the program it was
            row_sent()

    if wraps:
        pl.when(i == put)(row_sent)


def ring_attention(q, k_new, v_new, k_cache, v_cache, slot, length, *,
                   block, heads=None, scale=None, interpret=False,
                   wraps=False):
    """``q (B, H_q, d)``, ``k_new`` / ``v_new (B, H_kv, d)``, rings
    ``(slots, H_kv, d, max_len)``, ``slot`` / ``length (B,)`` int32 →
    ``(context (B, H_q, d), k_cache', v_cache')`` with the rings updated
    in place where the caller donates them.  `block` positions and
    `heads` K/V heads (default all) a grid step
    (``ops.attention.decode_block`` / ``decode_heads``, which also say
    for which rings the kernel's tiling holds: ``d_head`` divides 128 or
    is a multiple of it, a group's heads fill whole 128-line tiles);
    `interpret` runs Pallas's interpreter; `wraps`: the ring is a
    window's, written at ``length mod max_len`` and read whole once it is
    full.
    The caller jits (``ops.attention._decode_attention``): the layers of
    a decode program share one trace and one lowering of this."""
    bsz, h_q, d_head = q.shape
    slots, h_kv, _, max_len = k_cache.shape
    blk = int(block)
    nblk = max_len // blk
    groups = h_q // h_kv
    held = h_kv if heads is None else int(heads)   # K/V heads a grid step
    parts = h_kv // held
    hq = held * groups                             # query heads a grid step

    def page(b, j, i, slot_r, len_r):
        return slot_r[b], j, 0, jnp.minimum(i, len_r[b] // blk)

    def row(b, j, i, slot_r, len_r):
        return b, j, 0, 0

    def columns(x):
        """``(B, H, d)`` → ``(B, parts, d, H / parts)``: a head group's
        heads side by side, one column each."""
        return x.reshape(bsz, parts, -1, d_head).transpose(0, 1, 3, 2)

    cols = lambda n: pl.BlockSpec((1, 1, d_head, n), row)
    ring = pl.BlockSpec((1, held, d_head, blk), page)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    ctx, kc, vc = pl.pallas_call(
        functools.partial(_kernel, h_kv=held, groups=groups,
                          d_head=d_head, blk=blk, nblk=nblk, scale=scale,
                          wraps=wraps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, parts, nblk),
            in_specs=[cols(hq), cols(held), cols(held), ring, ring],
            out_specs=[pl.BlockSpec((1, 1, hq * d_head),
                                    lambda b, j, i, slot_r, len_r: (b, 0, j)),
                       hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((hq, d_head, _LANE), f32),     # q columns
                pltpu.VMEM((blk // _LANE, hq, _LANE), f32),  # scores, probs
                pltpu.VMEM((hq, _LANE), f32),             # running max
                pltpu.VMEM((hq, _LANE), f32),             # running sum
                pltpu.VMEM((hq, _LANE), f32),             # rescale
                pltpu.VMEM((hq, d_head, _LANE), f32),     # context
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((bsz, 1, h_q * d_head), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # the rings, read block by block and written where they lie
        # (operands count the two prefetched scalars)
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="kv_ring_attention",
        interpret=interpret,
    )(slot, length, columns(q), columns(k_new), columns(v_new), k_cache,
      v_cache)
    return ctx.reshape(bsz, h_q, d_head), kc, vc
