"""The absorbed decode step of latent attention (MLA) against the latent
ring as ONE Pallas TPU kernel a layer: a sibling of
``ops/kv_ring_kernel.py`` for a ring whose page is SHARED by all query
heads and whose value is a slice of its key.

The ring is stored ``(slots, 1, width, ring_len)`` — one row of `width`
floats a position, ``[c (rank) | k_r (width - rank)]``, positions on the
lanes (``TransformerLM.cache_spec`` owns the shape).  A grid step ``(b,
i)`` holds block `i` (`block` positions: ``ops.attention.decode_block``
with ``latent=True``) of row `b`'s page in VMEM, brought there by the
pipeline from ``(slot[b], 0, :, i * block)``; slot and length are
scalar-prefetched, and for `i` beyond the block that holds position
``length[b]`` the index map repeats that block, which the pipeline does
not fetch again, and the body does nothing: the blocks beyond are
SKIPPED, not masked.

The page is read ONCE for both products.  With ``Q (H, width)`` the
row's absorbed queries ``[q_nope_h W_kvb,h^T | q_rope_h]``:

    scores (H, block) = scale * Q  @ page            (all `width` lines)
    context (H, rank) = softmax(scores) @ page[:rank]^T   (its first `rank`)

online over the blocks — two matrix products a block on the matrix unit,
32 heads against one page where the per-head ring kernel does a
multiply-and-reduce a head on the vector unit (for 32 heads x 320 lines x
768 positions that would be six times the block's DMA).  They run at the
PROGRAM'S precision, JAX's default: on a TPU one bfloat16 pass of float32
operands with float32 accumulation, as the prefill's attention products
and every projection of the program are; the interpreter on the CPU
multiplies in float32.

The step's new row is one lane of every line: it is put into the block
that holds ``length[b]`` where that block lies in VMEM (one masked store)
BEFORE the row attends to it — write, then read: a token attends to
itself — and the 128 positions around it go back to their place in the
donated ring by one dense DMA, awaited at the end of the same grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_ring_attention"]

_LANE = 128
_NEG = -1e30          # ops/attention.py's mask value: finite


def _kernel(slot_ref, len_ref,                       # scalar prefetch
            q_ref, new_ref, page_ref,
            o_ref, ring_hbm,
            m_ref, l_ref, acc_ref, sem,
            *, rank, blk, scale):
    b, i = pl.program_id(0), pl.program_id(1)
    heads, width = q_ref.shape[1], q_ref.shape[2]
    length = len_ref[b]
    last = length // blk           # the block that holds the new row

    @pl.when(i == 0)
    def _start_row():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i == last)
    def _write_row():
        # the step's row is ONE LANE of this block: put it in where the
        # block lies in VMEM and send the 128 positions around it back to
        # the ring; the DMA runs under the arithmetic below
        off = length % blk
        chunk = pl.ds(pl.multiple_of(off // _LANE * _LANE, _LANE), _LANE)
        lane = lax.broadcasted_iota(jnp.int32, (width, _LANE), 1) \
            == off % _LANE
        column = jnp.broadcast_to(new_ref[0, 0], (width, _LANE))
        pltpu.store(page_ref.at[0, 0, :, chunk],
                    column.astype(page_ref.dtype), mask=lane)
        pltpu.make_async_copy(
            page_ref.at[0, 0, :, chunk],
            ring_hbm.at[slot_ref[b], 0, :,
                        pl.ds(pl.multiple_of(length // _LANE * _LANE, _LANE),
                              _LANE)],
            sem.at[0]).start()

    @pl.when(i <= last)
    def _attend():
        page = page_ref[0, 0]                               # (width, blk)
        s = jnp.dot(q_ref[0], page,
                    preferred_element_type=jnp.float32) * scale
        position = i * blk + lax.broadcasted_iota(jnp.int32, (heads, blk), 1)
        s = jnp.where(position <= length, s, _NEG)
        m_prev = m_ref[...]                                 # (heads, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        # the value is the row's first `rank` lines: the same block
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p, page_ref[0, 0, :rank, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == last)
    def _finish_row():
        o_ref[0] = acc_ref[...] / l_ref[:, :1]
        pltpu.make_async_copy(  # the wait needs the shapes only
            page_ref.at[0, 0, :, pl.ds(0, _LANE)],
            ring_hbm.at[0, 0, :, pl.ds(0, _LANE)], sem.at[0]).wait()


def latent_ring_attention(q, new, cache, slot, length, *, rank, block,
                          scale, interpret=False):
    """``q (B, H, width)`` absorbed queries, ``new (B, width)`` the
    step's latent rows, ring ``(slots, 1, width, ring_len)``, ``slot`` /
    ``length (B,)`` int32 → ``(context (B, H, rank), cache')`` with the
    ring updated in place where the caller donates it: each row's
    softmax over positions ``0..length`` of ``scale * q . row`` and the
    probabilities' sum of the rows' first `rank` lines.  `block`
    positions a grid step (``ops.attention.decode_block(..., latent=True)``,
    which also says for which rings the kernel's tiling holds); `interpret`
    runs Pallas's interpreter.  The caller jits
    (``ops.latent._latent_decode``): the layers of a decode program share
    one trace and one lowering of this."""
    bsz, heads, width = q.shape
    blk = int(block)
    nblk = cache.shape[3] // blk

    def page(b, i, slot_r, len_r):
        return slot_r[b], 0, 0, jnp.minimum(i, len_r[b] // blk)

    def row(b, i, slot_r, len_r):
        return b, 0, 0

    f32 = jnp.float32
    ctx, ring = pl.pallas_call(
        functools.partial(_kernel, rank=rank, blk=blk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, nblk),
            in_specs=[pl.BlockSpec((1, heads, width), row),
                      pl.BlockSpec((1, 1, width, 1),
                                   lambda b, i, slot_r, len_r: (b, 0, 0, 0)),
                      pl.BlockSpec((1, 1, width, blk), page)],
            out_specs=[pl.BlockSpec((1, heads, rank), row),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((heads, _LANE), f32),     # running max
                pltpu.VMEM((heads, _LANE), f32),     # running sum
                pltpu.VMEM((heads, rank), f32),      # context
                pltpu.SemaphoreType.DMA((1,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, rank), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        # the ring, read block by block and written where it lies
        # (operands count the two prefetched scalars)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="latent_ring_attention",
        interpret=interpret,
    )(slot, length, q, new[:, None, :, None], cache)
    return ctx, ring
