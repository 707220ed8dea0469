"""The delta rule's decode step as ONE Pallas TPU kernel a layer: each
packed row's state page is read once where it lies in the donated buffer,
advanced in VMEM and written back once.

The state is stored ``(slots, d_k, H d_v)`` — the key axis on the
sublanes, every head's values side by side on the lanes
(``TransformerLM.cache_spec`` owns the order) — and a grid step ``(b, j)``
holds block ``(slot[b], 0, j)`` of it, ``(1, d_k, heads d_v)``: `heads`
whole value heads (``ops.gdn.step_heads``) of row `b`'s page, brought
there by the pipeline and sent back by it; ``slot`` is scalar-prefetched
and the buffer is aliased to the output, so nothing but the rows' pages
moves.  Heads are independent and the rule's two products with the old
state reduce over ``d_k``, the sublanes: a group never needs another's
data, and everything below is elementwise along the lanes.

A row's keys and queries arrive as columns, ``(d_k, heads)`` — 128 x 32
floats where the spread-out operand XLA made for the ``jax.numpy`` body
was a page, ``(d_k, H d_v)``, a row and an operand — and a head's column is
spread over that head's ``d_v`` lanes in registers, one 128-lane tile of
the block at a time (a tile that two heads share — ``d_v`` 192: two heads
are three tiles — takes each head's column on that head's lanes).  The
values and the gates come a lane each, ``(4, heads d_v)``: ``v``, and
``alpha``, ``beta`` and ``k . q`` repeated over their head's lanes.  A
tile, with ``S`` its ``(d_k, 128)`` of the page:

    s_k = alpha sum_d (S k)      s_q = alpha sum_d (S q)
    write = beta (v - s_k)       o = s_q + write (k . q)
    S <- alpha S + k write^T

— ``ops/gdn.py`` has the algebra, and `_step_body` there is the same in
``jax.numpy`` and this kernel's oracle.  Float32 multiply-adds on the
vector unit, as the body compiles them; no matrix-unit pass, nothing
stored smaller.

Padded rows all name the scratch slot.  The pipeline fetches a row's
block while the row before it is still to be written, so such rows may
read one another's stale or half-written blocks: finite values, on the
scratch slot and nowhere else, which is the op's contract.  Live rows hold
distinct slots.

Measured on a TPU v5e (PERF.md section 6, PR 41).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["state_step"]

_F32 = jnp.float32
_LANE = 128


def _kernel(slot_ref, cols_ref, lanes_ref, s_ref, o_ref, so_ref,
            *, dk, dv, tiles):
    del slot_ref      # the index maps read it
    lane = lax.broadcasted_iota(jnp.int32, (dk, _LANE), 1)

    def spread(first_row, t):
        """Tile `t` of a ``(d_k, heads)`` operand spread along the
        block's lanes: each head's column on that head's `d_v` lanes."""
        rows = pl.ds(first_row, dk)
        head, last = t * _LANE // dv, (t * _LANE + _LANE - 1) // dv
        out = jnp.broadcast_to(cols_ref[0, 0, rows, pl.ds(head, 1)],
                               (dk, _LANE))
        for h in range(head + 1, last + 1):
            out = jnp.where(
                lane < h * dv - t * _LANE, out,
                jnp.broadcast_to(cols_ref[0, 0, rows, pl.ds(h, 1)],
                                 (dk, _LANE)))
        return out

    for t in range(tiles):
        at = slice(t * _LANE, (t + 1) * _LANE)
        page = s_ref[0, :, at].astype(_F32)
        k, q = spread(0, t), spread(dk, t)
        v, alpha, beta, kq = (lanes_ref[0, n:n + 1, at] for n in range(4))
        s_k = jnp.sum(page * k, axis=0, keepdims=True) * alpha
        s_q = jnp.sum(page * q, axis=0, keepdims=True) * alpha
        write = beta * (v - s_k)
        o_ref[0, :, at] = s_q + write * kq
        so_ref[0, :, at] = (page * alpha + k * write).astype(so_ref.dtype)


def state_step(k, q, v, alpha, beta, state, slot, *, heads, interpret=False):
    """``k`` / ``q (B, H, d_k)`` normalized and repeated to value heads,
    ``v (B, H, d_v)``, ``alpha`` / ``beta (B, H)``, ``state (slots, d_k, H
    d_v)``, ``slot (B,)`` int32 → ``(o (B, H, d_v), state')`` with row b's
    page advanced by one position at ``slot[b]``, in place where the
    caller donates the buffer.  `heads` value heads a grid step
    (``ops.gdn.step_heads``, which also says for which states the
    kernel's tiling holds); `interpret` runs Pallas's interpreter.  The
    caller jits (``ops.gdn._state_step``): the delta-rule layers of a
    decode program share one trace and one lowering of this."""
    rows, h, dk = k.shape
    dv = v.shape[-1]
    held = int(heads)
    parts = h // held
    width = held * dv

    def columns(x):
        """``(B, H, d_k)`` → ``(B, parts, d_k, held)``: a group's heads
        side by side, one column each."""
        return x.reshape(rows, parts, held, dk).transpose(0, 1, 3, 2)

    wide = lambda x: jnp.repeat(x, dv, axis=-1)       # (B, H) -> (B, H d_v)
    cols = jnp.concatenate([columns(k), columns(q)], axis=2).astype(_F32)
    lanes = jnp.stack([v.reshape(rows, h * dv), wide(alpha), wide(beta),
                       wide(jnp.sum(k * q, axis=-1))], axis=1).astype(_F32)
    page = pl.BlockSpec((1, dk, width), lambda b, j, slot_r: (slot_r[b], 0, j))
    o, state = pl.pallas_call(
        functools.partial(_kernel, dk=dk, dv=dv, tiles=width // _LANE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, parts),
            in_specs=[pl.BlockSpec((1, 1, 2 * dk, held),
                                   lambda b, j, slot_r: (b, j, 0, 0)),
                      pl.BlockSpec((1, 4, width),
                                   lambda b, j, slot_r: (b, 0, j)),
                      page],
            out_specs=[pl.BlockSpec((1, 1, width),
                                    lambda b, j, slot_r: (b, 0, j)),
                       page]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, h * dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state, read and written page by page where it lies
        # (operands count the prefetched scalar)
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="gdn_state_step",
        interpret=interpret,
    )(slot, cols, lanes, state)
    return o.reshape(rows, h, dv), state
