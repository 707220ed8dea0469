"""The Mamba-2 decode step's state update as ONE Pallas TPU kernel a layer:
each packed row's state page is read once where it lies in the donated
buffer, advanced in VMEM and written back once.

The state is stored ``(slots, H, P, S)`` float32 — a head's ``P`` channels
on the sublanes, its ``S`` states on the lanes (``TransformerLM.cache_spec``
owns the order) — and a grid step ``(b, j)`` holds block ``(slot[b], j)`` of
it, ``(1, heads, P, S)``: `heads` whole heads (``ops.ssm.step_heads``) of
row `b`'s page, brought there by the pipeline and sent back by it;
``slot`` is scalar-prefetched and the buffer is aliased to the output, so
nothing but the rows' pages moves.  Heads are independent, and a head, with
``page`` its ``(P, S)`` of the block:

    page <- page * decay[h] + dtx[h, p] * b[s]
    y[h, p] = sum_s page[h, p, s] * c[s]

— ``ops/ssm.py`` has the equations, and `_step_body` there is the same in
``jax.numpy`` and this kernel's oracle.  A head's ``decay`` is a scalar
(prefetched beside ``slot``), its ``dtx`` arrives as a column, ``(P,
heads)`` a block — a head's ``P`` values down the sublanes, spread over the
lanes in registers — and its group's ``b`` and ``c`` as rows of ``S`` lanes,
spread over the sublanes; ``y`` leaves as it came, a column a head.  Float32
multiply-adds on the vector unit, as the body compiles them, the sum over
the states a float32 reduction along the lanes; no matrix-unit pass, nothing
stored smaller.

Padded rows all name the scratch slot.  The pipeline fetches a row's
block while the row before it is still to be written, so such rows may
read one another's stale or half-written blocks: finite values, on the
scratch slot and nowhere else, which is the op's contract.  Live rows hold
distinct slots.

Measured on a TPU v5e (PERF.md section 6, PR 58).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["state_step"]

_F32 = jnp.float32


def _kernel(slot_ref, decay_ref, cols_ref, rows_ref, s_ref, y_ref, so_ref,
            *, heads, per_group):
    del slot_ref      # the index maps read it
    row, first = pl.program_id(0), pl.program_id(1) * heads
    p, s = s_ref.shape[2:]
    groups = rows_ref.shape[2] // 2
    for h in range(heads):
        g = h // per_group         # 0 where the block lies in one group
        b = rows_ref[0, 0, g:g + 1, :]
        c = rows_ref[0, 0, groups + g:groups + g + 1, :]
        dtx = jnp.broadcast_to(cols_ref[0, 0, :, h:h + 1], (p, s))
        page = s_ref[0, h].astype(_F32) * decay_ref[row, first + h] + dtx * b
        y_ref[0, 0, :, h:h + 1] = jnp.sum(page * c, axis=1, keepdims=True)
        so_ref[0, h] = page.astype(so_ref.dtype)


def state_step(dtx, decay, b, c, state, slot, *, heads, interpret=False):
    """``dtx (B, H, P)`` — ``dt x``, a head's input to its state —, ``decay
    (B, H)``, ``b`` / ``c (B, G, S)`` a group's (``H / G`` heads share
    one), ``state (slots, H, P, S)``, ``slot (B,)`` int32 → ``(y (B, H, P),
    state')`` with row b's page advanced by one position at ``slot[b]``, in
    place where the caller donates the buffer (on a TPU the caller
    does: see the note at ``out_shape``); ``y`` without the ``D x`` term.  `heads` heads a grid step (``ops.ssm.step_heads``, which also
    says for which states the kernel's tiling holds: a block's heads lie
    in one group or are whole groups); `interpret` runs Pallas's
    interpreter.  The caller jits (``ops.ssm._state_step``): the Mamba-2
    layers of a decode program share one trace and one lowering of this."""
    rows, h, p = dtx.shape
    groups, s = b.shape[1:]
    held = int(heads)
    parts = h // held
    per_group = h // groups
    # the groups a block's heads read: whole ones, or the one they lie in
    if held >= per_group:
        split = lambda x: x.reshape(rows, parts, held // per_group, s)
    else:
        split = lambda x: jnp.repeat(x, per_group // held, axis=1)[:, :, None]
    lanes = jnp.concatenate([split(b), split(c)], axis=2).astype(_F32)
    cols = dtx.reshape(rows, parts, held, p).transpose(0, 1, 3, 2).astype(_F32)
    small = lambda shape: pl.BlockSpec(
        (1, 1) + shape, lambda r, j, *prefetched: (r, j, 0, 0))
    page = pl.BlockSpec((1, held, p, s),
                        lambda r, j, slot_r, decay_r: (slot_r[r], j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=held, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, parts),
            in_specs=[small((p, held)), small(lanes.shape[2:]), page],
            out_specs=[small((p, held)), page]),
        # the state stays in HBM, the operand with the output it is
        # aliased to: left to itself XLA stages a whole 37.7 MB buffer
        # through its fast memory around the call, for four of
        # granite-4.0-h-small's nine layers (PERF.md section 6, PR 58).
        # The price: a program that is this call ALONE and does not donate
        # the state aborts XLA's memory-space assignment (libtpu 0.0.34,
        # algorithm.cc:5304 — it wants its copy of the state in fast
        # memory); a whole decode program compiles donated or not, and a
        # session's programs donate
        out_shape=[jax.ShapeDtypeStruct((rows, parts, p, held), _F32),
                   pltpu.HBM(state.shape, state.dtype)],
        # read and written page by page where it lies (operands count
        # the prefetched scalars)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="ssm_step_kernel",
        interpret=interpret,
    )(slot, decay.astype(_F32), cols, lanes, state)
    return y.transpose(0, 1, 3, 2).reshape(rows, h, p), state
