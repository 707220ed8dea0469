"""A sequence's attention under a MASK THAT IS AN OPERAND as ONE Pallas TPU
kernel a group of heads: the whole-sequence form of the learned selection
(``ops/sparse_latent.py _attend_masked``), blockwise under an online
softmax, and no score ever written to HBM.

``q (G, Tq, dk)`` and ``k (G, T, dk)`` — a head's key as wide as its query,
``[k_nope | k_r]`` with the ONE rotary row of a position laid beside every
head's own part —, ``v (G, T, dv)`` of another width, and ``keep (Tq, T)``
int8, nonzero where row t attends to position s: the selection's mask,
causal already, the same for every head.  A grid step ``(g, i, j)`` holds
`rows` query positions (block `i`) of `heads` heads, ONE block of `keys`
positions of their K and V, and that block of `keep` — read once for all
the step's heads.  The last grid axis walks the key blocks: K and V are
STREAMED, a block a step (a head's whole K and V of 15,360 positions,
twice over in the pipeline, are more VMEM than a kernel may ask for), and
re-read once a query block.  A key block wholly above the diagonal is NOT
VISITED: its block index is clamped at the diagonal's, so the pipeline
brings nothing new, and ``pl.when`` skips the step.  Across the visited
blocks a running maximum, a running sum and a rescaled accumulator per
row, all float32, as ``ops/sdp_kernel.py`` keeps them:

    s = scale * Q K_j^T  where keep, else -1e30      the matrix unit
    m' = max(m, rowmax s);  p = exp(s - m');  a = exp(m - m')
    l = a l + rowsum p;     acc = a acc + P V_j      the matrix unit

and ``acc / l`` is written at the diagonal's block.  Both products take
their operands as they come and accumulate in float32; the probabilities
go to the values' dtype for theirs.  The caller hands the TPU's kernel
bfloat16 — what one pass of the matrix unit makes of the program's float32
operands anyway — so the only difference from the ``jax.numpy`` body is
the order of a row's sum (and that a probability is rounded before the
row's sum divides it, not after).  The mask value is the body's finite
``-1e30``: a row that keeps nothing of the blocks visited so far carries
garbage (``p = exp(0)``) until a kept score arrives, whose ``a = exp(-1e30
- m')`` is exactly 0 — every real row keeps a position —, and a pad's row,
which keeps nothing at all, ends as the mean of the values it visited:
finite, no NaN, and cut by the caller.

What was measured on a TPU v5e is in PERF.md section 6, PR 49.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["masked_attention"]

_F32 = jnp.float32
_NEG = -1e30          # ops/attention.py's mask value: finite


def _diagonal(i, rows, keys, blocks):
    """The last key block that query block `i`'s rows can see."""
    return jnp.minimum((i * rows + rows - 1) // keys, blocks - 1)


def _kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref, l_ref, acc_ref,
            *, rows, keys, scale):
    i, j = pl.program_id(1), pl.program_id(2)
    end = _diagonal(i, rows, keys, pl.num_programs(2))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    @pl.when(j <= end)
    def _():
        kept = keep_ref[...].astype(jnp.int32) != 0
        for h in range(q_ref.shape[0]):      # the step's heads, one mask
            s = lax.dot_general(q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
            s = jnp.where(kept, s, _NEG)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            v = v_ref[h]
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=_F32)

    @pl.when(j == end)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...]


def masked_attention(q, k, v, keep, *, rows, keys, heads, scale,
                     interpret=False):
    """Attention of ``q (G, Tq, dk)`` over ``k (G, T, dk)`` / ``v (G, T,
    dv)`` under ``keep (Tq, T)`` int8 (nonzero: attend; no key above the
    diagonal kept): ``[context (G, Tq, dv)]`` in float32, row t of every
    head the softmax of ``scale * q_t . k_s`` over the kept ``s`` times
    ``v``.  `rows` query positions (dividing ``Tq``) of `heads` heads
    (dividing ``G``) a grid step and `keys` positions a key block
    (dividing ``T``): ``ops.sparse_latent.masked_block`` says for which
    shapes, and gives them; `interpret` runs Pallas's interpreter.  The
    caller jits."""
    g, tq, dk = q.shape
    t, dv = v.shape[1:]
    rows, keys, heads = int(rows), int(keys), int(heads)
    blocks = t // keys

    def seen(i, j):   # a block above the diagonal is the diagonal's again
        return jnp.minimum(j, _diagonal(i, rows, keys, blocks))

    mine = lambda width: pl.BlockSpec(                        # noqa: E731
        (heads, rows, width), lambda b, i, j: (b, i, 0))
    theirs = lambda width: pl.BlockSpec(                      # noqa: E731
        (heads, keys, width), lambda b, i, j: (b, seen(i, j), 0))
    visited = sum(min((i * rows + rows - 1) // keys, blocks - 1) + 1
                  for i in range(tq // rows))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, keys=keys, scale=float(scale)),
        grid=(g // heads, tq // rows, blocks),
        in_specs=[mine(dk), theirs(dk), theirs(dv),
                  pl.BlockSpec((rows, keys), lambda b, i, j: (i, seen(i, j)))],
        out_specs=[mine(dv)],
        scratch_shapes=[pltpu.VMEM((heads, rows, 1), _F32),   # running maximum
                        pltpu.VMEM((heads, rows, 1), _F32),   # running sum
                        pltpu.VMEM((heads, rows, dv), _F32)],  # the context
        out_shape=[jax.ShapeDtypeStruct((g, tq, dv), _F32)],
        # as much as the other prefill kernels ask for, no more: what a
        # kernel may use, XLA may not keep activations in across it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * g * visited * rows * keys * (dk + dv),
            transcendentals=g * visited * rows * keys,
            bytes_accessed=(
                q.size * q.dtype.itemsize + 4 * g * tq * dv
                + visited * keys * (g * (dk + dv) * k.dtype.itemsize
                                    + rows * g // heads))),
        name="masked_latent_attention",
        interpret=interpret,
    )(q, k, v, keep)
