"""A prompt's causal attention as ONE Pallas TPU kernel a layer: the
positions of a sequence among themselves, blockwise under an online
softmax, and no score ever written to HBM.

``q (N, H_kv, r, T, d)`` — the `r` query heads of each K/V head side by
side, groups of one being plain multi-head attention — and ``k`` / ``v (N,
H_kv, T, d)`` as ``ops.attention.sdp_attention`` makes them.  A grid step
``(n, g, i)`` holds `rows` query positions (block `i`) of ALL `r` heads of
group `g` as one ``(r * rows, d)`` operand, and that K/V head's WHOLE ``(T,
d)`` key and value in VMEM: the pipeline brings them once a head (their
block index does not change with `i`), so a K/V head is read once for all
the query heads of its group.  The step walks the key blocks of `keys`
positions the rows can see and no others — those wholly above the
diagonal, or wholly outside a `window`, are NOT VISITED, and of the rest
only the blocks the diagonal or the window's edge crosses are masked —
keeping a running maximum, a running sum and a rescaled accumulator per
row, all float32:

    s = scale * Q K_j^T          (r rows, keys)    the matrix unit
    m' = max(m, rowmax s);  p = exp(s - m');  a = exp(m - m')
    l = a l + rowsum p;     acc = a acc + P V_j    the matrix unit

and writes ``acc / l`` once.  Both products take their operands as they
come and accumulate in float32; the probabilities go to the values' dtype
for theirs.  ``ops.attention`` hands the TPU's kernel bfloat16 — what one
pass of the matrix unit makes of the program's float32 operands anyway —
so the only difference from the ``jax.numpy`` body is the order of a row's
sum (and that a probability is rounded before the row's sum divides it,
not after).  The mask value is the body's finite ``-1e30``: a row whose
first visited block is masked whole carries garbage until a real score
arrives, whose ``a = exp(-1e30 - m')`` is exactly 0.

What was measured on a TPU v5e is in PERF.md section 6, PR 47.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["causal_attention", "visited"]

_F32 = jnp.float32
_NEG = -1e30          # ops/attention.py's mask value: finite


def visited(first, rows, keys, window, maximum=max):
    """The key blocks ``[lo, end)`` of `keys` positions that the grid step
    of query rows ``first .. first + rows`` walks: up to its last row's
    block and, under a `window`, from the block that holds the first row's
    oldest visible key, ``first - window + 1``.  THE rule of what the
    kernel skips: the kernel walks these bounds (`first` traced, `maximum`
    ``jnp.maximum``) and ``ops.attention.prefill_visits`` counts them (plain
    integers)."""
    end = (first + rows - 1) // keys + 1
    if window is None:
        return 0, end
    return maximum(first - window + 1, 0) // keys, end


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, rows, keys, scale, window):
    i = pl.program_id(2)
    group, _, d = q_ref.shape[2:]
    q = q_ref[0, 0].reshape(group * rows, d)
    first = i * rows                      # the block's first position
    # key position less query position, block 0 against block 0
    apart = (lax.broadcasted_iota(jnp.int32, (group, rows, keys), 2)
             - lax.broadcasted_iota(jnp.int32, (group, rows, keys), 1)
             ).reshape(group * rows, keys)

    m_ref[...] = jnp.full(m_ref.shape, _NEG, _F32)
    l_ref[...] = jnp.zeros(l_ref.shape, _F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def block(j, masked):
        at = pl.ds(pl.multiple_of(j * keys, keys), keys)
        s = lax.dot_general(q, k_ref[0, 0, at, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32) * scale
        if masked:
            gap = apart + (j * keys - first)
            keep = gap <= 0
            if window is not None:
                keep &= gap > -window
            s = jnp.where(keep, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # mxlint: disable=E006 -- a Pallas Ref: the store is the kernel's write to VMEM, staged into the loop body
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        # mxlint: disable=E006 -- a Pallas Ref, as above
        m_ref[...] = m_new
        v = v_ref[0, 0, at, :]
        # mxlint: disable=E006 -- a Pallas Ref, as above
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=_F32)

    def walk(lo, hi, masked):
        def body(j, carry):
            block(j, masked)
            return carry
        lax.fori_loop(lo, hi, body, 0)

    # blocks [lo, end) are visible; of them [clear, diagonal) wholly so
    lo, end = visited(first, rows, keys, window, jnp.maximum)
    diagonal = (first + 1) // keys
    if window is None:
        clear = 0
    else:
        clear = jnp.clip(
            jnp.maximum(first + rows - 1 - window + keys, 0) // keys, lo,
            diagonal)
        walk(lo, clear, True)
    walk(clear, diagonal, False)
    walk(diagonal, end, True)
    o_ref[0, 0] = (acc_ref[...] / l_ref[...]).reshape(group, rows, d)


def causal_attention(q, k, v, *, rows, keys, scale, window=None,
                     interpret=False):
    """Causal attention of ``q (N, H_kv, r, T, d)`` over ``k`` / ``v (N,
    H_kv, T, d)``: ``[context (N, H_kv, r, T, d)]`` in float32, row t of
    every head the softmax of ``scale * q_t . k_s`` over ``s <= t`` — with
    `window` W only ``t - s < W`` — times ``v``.  `rows` query positions a
    grid step and `keys` positions a key block, both dividing ``T``
    (``ops.attention.prefill_block`` says for which shapes, and gives the
    two); `interpret` runs Pallas's interpreter.  The caller jits."""
    n, h_kv, group, t, d = q.shape
    rows, keys = int(rows), int(keys)
    wide = group * rows
    whole = pl.BlockSpec((1, 1, t, d), lambda b, g, i: (b, g, 0, 0))
    mine = pl.BlockSpec((1, 1, group, rows, d),
                        lambda b, g, i: (b, g, 0, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, keys=keys, scale=float(scale),
                          window=None if window is None else int(window)),
        grid=(n, h_kv, t // rows),
        in_specs=[mine, whole, whole],
        out_specs=[mine],
        scratch_shapes=[pltpu.VMEM((wide, 1), _F32),    # running maximum
                        pltpu.VMEM((wide, 1), _F32),    # running sum
                        pltpu.VMEM((wide, d), _F32)],   # the context
        out_shape=[jax.ShapeDtypeStruct(q.shape, _F32)],
        # as much as the delta rule's kernel asks for, no more: what a
        # kernel may use, XLA may not keep activations in across it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * h_kv * group * t * t * d,
            transcendentals=n * h_kv * group * t * t // 2,
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize
            + 4 * q.size),
        name="sdp_causal_attention",
        interpret=interpret,
    )(q, k, v)
