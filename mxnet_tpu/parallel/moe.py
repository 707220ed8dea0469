"""Expert parallelism — Mixture-of-Experts over an 'expert' mesh axis.

Completes the named-strategy set (DP/TP/SP/PP/EP; SURVEY.md §2.5 marks EP
absent from the 2017 reference).  The TPU-idiomatic design: experts are
sharded one-per-device-group along an 'expert' mesh axis, tokens are
routed with a capacity-bounded top-k gate, and the dispatch/combine is
`lax.all_to_all` — the collective that rides ICI all-to-all links on a
TPU torus (the same primitive Ulysses SP uses, parallel/ring_attention.py).

Pieces:
  * router_logits / dropless_experts — the dropless layer of ONE device
    (what today's open MoE decoders run): every token-expert pair is
    computed.  Pairs are sorted by expert and each expert multiplies its
    own contiguous segment (`segment_matmul`: `lax.ragged_dot` — on the
    TPU a grouped matmul that visits only the tiles a non-empty segment
    touches, so a decode step reads the experts that were hit — or, from
    `_KERNEL_ROWS` rows an expert on a TPU, the kernel of
    ops/grouped_matmul_kernel.py, which walks each expert's own rows);
    no [T, E, C] tensor exists.  A model that holds a
    RANGE of the router's experts sorts every pair and walks the held
    ones alone, in passes of a static row count (`_held_passes`).
  * top_k_gating(logits, k, capacity) — deterministic capacity-bounded
    router (Switch/GShard-style): per-expert position via a cumulative
    count, tokens over capacity dropped (combine weight 0).
  * moe_apply(...)    — per-shard body, call inside shard_map: dispatch
    tokens to local experts via all_to_all, apply, combine back.
  * moe_sharded(...)  — host-level wrapper building the shard_map over
    ('expert',) or ('data','expert').

Everything is static-shaped (capacity fixes the buffer sizes) so the
whole layer jits into one XLA program — no data-dependent shapes.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import exported
from .collectives import axis_size, shard_map, shard_map_unchecked
from .mesh import P

__all__ = ["router_logits", "dropless_experts", "top_k_gating", "moe_apply",
           "moe_sharded"]

ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def router_logits(x, gate_w):
    """Router scores [T, E] in float32: the matmul at `highest`
    precision whatever the surrounding default (D x E a token — a
    bfloat16 pass here moves which experts a token gets)."""
    return jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def expert_ffn(matmul, x, weights, biases, act, gated):
    """One expert FFN over rows that `matmul(rows, w)` / `biases(b)` map
    to their experts: ``act(x w1 + b1) [* (x w3 + b3)] -> w2 + b2``.
    `weights` is (w1, w2[, w3]); `biases` the same order or None."""
    def lin(rows, i):
        y = matmul(rows, weights[i])
        return y if biases is None else y + biases[i]

    h = ACTIVATIONS[act](lin(x, 0))
    if gated:
        h = h * lin(x, 2)
    return lin(h, 1)


# the bytes of the pair rows a call GATHERS at once — every pair's T*k rows,
# or, where a held range walks its held pairs alone (`_pass_rows`), one
# pass's: a bucket of 2,048 positions at eight experts a token and a hidden
# size of 4,096 is within it; a bucket of fifteen thousand (eight a token at
# 5,120) whose every pair's row would be 2.5 GB a copy is too where eight of
# its 256 experts are held (one pass of 6,144 rows, 126 MB); whatever is not
# goes through in equal pieces of its tokens, one after the other
_PAIR_BYTES = 256 << 20
# How many rows `_dropless` gathers for its pairs.  The TPU's grouped matmul
# (`lax.ragged_dot`) walks the sorted pair rows by the largest power-of-two
# tile, up to `_ROW_TILE`, that divides their count, one (tile, expert) item
# at a time: by the large tile an item multiplies 512 rows whatever the
# expert's segment holds of them (~12.5 us at OLMoE's widths), by a small one
# it re-reads the expert's weights for every tile the segment touches (~9.9
# us a 64-row item).  So the large tile pays from about `_ROWS_AN_EXPERT`
# pairs an expert scored (PERF.md section 6, PR 53, a dot in its program:
# the small tile 10% shorter at 8 pairs an expert, the two level at 16 and
# at 30, the large one 15% shorter at 32 and 46% at 65), and from there the
# count is made a whole number of large tiles — a mixed step's (T + rows) x
# k pairs are never one by themselves; fewer pairs are left as they are.
# XLA documents none of this: both numbers were read off the chip under
# jaxlib 0.9.0 / libtpu 0.0.34, and `tests/test_tpu_compile.py` reads the
# tile the compiler picks back from the compiled dot.
_ROW_TILE = 512
_ROWS_AN_EXPERT = 24


def _spare_rows(pairs, experts):
    """The rows to gather beyond `pairs` pairs routed over `experts`."""
    if pairs < max(_ROW_TILE, _ROWS_AN_EXPERT * experts):
        return 0
    return -pairs % _ROW_TILE


# From `_KERNEL_ROWS` sorted rows an expert whose matrix is an operand, a
# call's segment matmul is OURS on a TPU (`ops/grouped_matmul_kernel.py`):
# it walks each expert's own rows by a tile of `_KERNEL_TILE` and reads a
# matrix once, where XLA's multiplies a whole 512-row tile for every expert
# whose segment touches it — two to three times the rows there are at a few
# hundred an expert — or re-reads the matrix a 64-row tile.  Read off the
# chip at the eight routed cells' shapes, a routed FFN's three dots a call
# (`chip_smoke.py` `grouped_matmul`, PERF.md section 6, PR 60; jaxlib 0.9.0
# / libtpu 0.0.34), the kernel's time over XLA's: 0.99-1.06 at a decode
# step's 1-4 rows an expert (both read the hit experts' matrices once),
# 0.83-0.93 at 9, 0.82 at 17, 0.72-0.84 at 24-96, 0.62-0.69 at 114-228,
# 0.44-0.48 at SmallThinker's 216-384.  16 is between the level readings
# and the first clear one: every decode step of the cells (at most 8.9
# rows an expert: 80 pairs over nine) stays XLA's, every prefill from 17
# is ours.  `_KERNEL_BLOCK` bounds the float32 bytes of the part of a
# matrix the kernel holds at a time, twice: the contraction whole, as many
# of the columns as fit.
_KERNEL_ROWS = 16
_KERNEL_TILE = 128
_KERNEL_BLOCK = 16 << 20
_LANES = 128


def kernel_tiles(rows, experts, k, n):
    """``(tm, tn)``, the row tile and the columns a strip of
    ``ops.grouped_matmul_kernel.grouped_matmul`` for a call's segment
    matmul — `rows` sorted rows ``[rows, k]``, `experts` matrices ``[k,
    n]`` as operands — or None where the call is `lax.ragged_dot`'s: fewer
    than `_KERNEL_ROWS` rows an expert (every decode step), or widths
    that are no whole 128-lane tiles.  Static shapes in, nothing else:
    whoever counts what a program runs (`TransformerLM.expert_plan`) asks
    here too."""
    if rows < _KERNEL_ROWS * experts or k % _LANES or n % _LANES:
        return None
    tn = next((tn for tn in range(n, 0, -_LANES)
               if n % tn == 0 and 4 * k * tn <= _KERNEL_BLOCK), None)
    return tn and (_KERNEL_TILE, tn)


def _ragged_dot(rows, w, sizes):
    return lax.ragged_dot(rows, w.astype(rows.dtype), sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_matmul(tiles, interpret, rows, w, sizes):
    def kernel(rows, w, sizes):
        # lowered once a shape for all programs and processes
        # (ops/exported.py)
        out, = exported.call("grouped_matmul_kernel", "grouped_matmul",
                             (rows, w, sizes), interpret=interpret,
                             tm=tiles[0], tn=tiles[1])
        return out
    return lax.platform_dependent(rows, w, sizes, tpu=kernel,
                                  default=_ragged_dot)


def _kernel_matmul_fwd(tiles, interpret, rows, w, sizes):
    return _kernel_matmul(tiles, interpret, rows, w, sizes), (rows, w, sizes)


def _kernel_matmul_bwd(tiles, interpret, operands, cotangent):
    # the kernel has no backward: a gradient takes `lax.ragged_dot`'s
    rows, w, sizes = operands
    _, vjp = jax.vjp(lambda rows, w: _ragged_dot(rows, w, sizes), rows, w)
    return vjp(cotangent) + (np.zeros(sizes.shape, jax.dtypes.float0),)


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)

# Pallas's interpreter in place of the TPU's kernel (tests)
_INTERPRET = False


def segment_matmul(rows, w, sizes):
    """``rows [M, K]`` sorted by expert times each expert's matrix of ``w
    [E, K, N]``, ``sizes [E]`` rows an expert → ``[M, N]``: one contraction
    with two implementations, chosen by the call's static shape
    (`kernel_tiles`) and the platform the program is lowered for — the
    TPU's kernel, or `lax.ragged_dot`, which is also every platform's
    backward and what a call under the rule is traced as, with no trace of
    the choice.  Rows past the last segment hold whatever either leaves
    there: the callers set them to 0."""
    tiles = kernel_tiles(rows.shape[0], *w.shape)
    if tiles is None:
        return _ragged_dot(rows, w, sizes)
    return _kernel_matmul(tiles, _INTERPRET, rows, w, sizes)


# Under a held range only `count` of the router's `experts` experts are here,
# and the sort puts their pairs first: the `width`-wide work — the gather of
# the rows, the three grouped matmuls, the weights, the return to token order
# — then runs over passes of a STATIC count of sorted rows, and as many
# passes as the held pairs fill.  The count is the uniform expectation
# `pairs * count / experts` and half as much again, in whole `_ROW_TILE`s
# (the count is ours to choose, so the grouped matmul walks the large tile):
# one pass for any routing near uniform, more under skew, every pair in
# some pass.  It is taken where it saves whole tiles of a call that has a
# few, from `_COMPACT_PAIRS` pairs: read off the chip (PERF.md section 6,
# PR 55), Granite-H-Small's 128-position bucket — 1,280 pairs, a pass of
# 512 — is level with gathering every pair's row, its 256 bucket a fifth
# shorter and its 1,024 bucket 30%; a decode step's 32 to 320 pairs are
# under it and compile to what they did.
_PASS_SLACK = 1.5
_COMPACT_PAIRS = 2 * _ROW_TILE


def _pass_rows(pairs, held, experts):
    """The sorted rows a pass of `_held_passes` takes of `pairs` pairs
    routed over `experts` experts of which `held` ``(first, count)`` are
    here; 0 where `_dropless` gathers every pair's row (no held range,
    few pairs, or no whole tile saved)."""
    if held is None or pairs < _COMPACT_PAIRS:
        return 0
    rows = _ROW_TILE * math.ceil(
        _PASS_SLACK * pairs * held[1] / experts / _ROW_TILE)
    return rows if rows < pairs else 0


def pass_plan(tokens, k, row_bytes, held, experts):
    """How `dropless_experts` goes through `tokens` tokens of `row_bytes`
    a row: ``(pieces, rows)`` — in `pieces` equal pieces of the tokens,
    the fewest whose gathered rows are within `_PAIR_BYTES` each, a piece
    in passes of `rows` sorted rows (`_pass_rows`; 0: every pair's row at
    once).  The serving path's counters read the same plan."""
    if k * row_bytes > _PAIR_BYTES:
        raise ValueError("one token's %d pair rows are %d bytes: no piece "
                         "is within %d" % (k, k * row_bytes, _PAIR_BYTES))
    for pieces in range(1, max(tokens, 1) + 1):
        if tokens % pieces == 0:
            pairs = tokens // pieces * k
            rows = _pass_rows(pairs, held, experts)
            if (rows or pairs) * row_bytes <= _PAIR_BYTES:
                return pieces, rows


def _held_passes(x, order, sorted_e, load, top_w, rows, weights, biases,
                 act, gated):
    """The held experts' part of `_dropless` over the held pairs alone:
    `order [T*k]` the pairs sorted by held expert (the others behind
    them), `load [count]` each held expert's pairs.  Pass p takes sorted
    rows ``p * rows .. (p + 1) * rows``: it gathers their tokens' rows of
    `x`, multiplies each expert's part of the window by its weights,
    weighs the results by the router and adds them to their tokens' rows
    of the result.  Passes run while held pairs are left — a `lax.scan`
    over the most there can be whose empty passes a `lax.cond` skips, so
    the layer stays reverse-differentiable — and consecutive passes meet
    consecutive experts: the held weights are read about once.  Returns
    out [T, D]."""
    t_len, k = top_w.shape
    held_pairs = load.sum()
    ends = jnp.cumsum(load)
    passes = -(-len(order) // rows)
    pad = passes * rows - len(order)
    order = jnp.pad(order, (0, pad))
    flat_w = top_w.reshape(-1)
    if biases is not None:
        sorted_e = jnp.pad(sorted_e, (0, pad))
        biases = [b.astype(x.dtype) for b in biases]

    def one(out, first):
        def run(out):
            pair = lax.dynamic_slice(order, (first,), (rows,))
            # rows past the last held pair belong to no segment: whatever
            # the grouped matmul leaves there, forward or backward (zeros
            # on the CPU, stale memory on a TPU), is set to 0 and comes
            # from and goes to row T, nobody's
            live = first + jnp.arange(rows) < held_pairs
            token = jnp.where(live, pair // k, t_len)
            window = (jnp.clip(ends, first, first + rows)
                      - jnp.clip(ends - load, first, first + rows))

            def matmul(r, w):
                return segment_matmul(r, w, window)

            of_rows = None
            if biases is not None:
                expert = lax.dynamic_slice(sorted_e, (first,), (rows,))
                of_rows = [b[expert] for b in biases]
            ys = expert_ffn(matmul, x.at[token].get(mode="fill",
                                                    fill_value=0),
                            weights, of_rows, act, gated)
            # on the vector unit: a matmul would round the scores to bfloat16
            ys = (jnp.where(live[:, None], ys, 0)
                  * flat_w[pair].astype(ys.dtype)[:, None])
            return _return_rows(out, ys, token, held_pairs - first)

        return lax.cond(first < held_pairs, run, lambda out: out, out), None

    out = jnp.zeros((t_len, weights[1].shape[-1]), x.dtype)
    return lax.scan(one, out, jnp.arange(passes) * rows)[0]


# (What PR 61 adds stands BELOW `_held_passes`: the exported kernel's body
# holds the source lines of the calls that reach it, so a held range's
# programs stay the parent's byte for byte while the lines above do.)
def fused_tile(rows, experts, d, h, gated):
    """The row tile of the TWO kernel calls a routed FFN is where its
    calls fetch and place their own rows (``ops.grouped_matmul_kernel``
    `gate_up` / `down`, PR 61) — `rows` sorted rows, `experts` FFNs of
    ``[d, h]`` (`gated`: two such an expert) and ``[h, d]`` as operands —
    or None: a shape the kernel does not take (`kernel_tiles`), matrices
    an expert that `_KERNEL_BLOCK` does not hold WHOLE (the two calls walk
    no strips: such a layer keeps the three calls), or more rows than
    `_FUSED_ROWS`.  `_dropless` says which calls may ask."""
    if (kernel_tiles(rows, experts, d, h) is None or rows > _FUSED_ROWS
            or 4 * d * h * (1 + bool(gated)) > _KERNEL_BLOCK):
        return None
    return _KERNEL_TILE


# the sorted rows' tokens and places are scalars the two calls read from
# the TPU's scalar memory, whole (a v5e's is 1 MiB: compiled for a described
# one, a call of 131,072 rows fits beside the walk and one of 262,144 does
# not; jaxlib 0.9.0 / libtpu 0.0.34) — half of what was seen to fit
_FUSED_ROWS = 1 << 16


def _every_pair(matmul, x, order, sorted_e, load, top_w, weights, biases, act,
                gated, spare, held):
    """The experts' part of `_dropless` over every pair's row: gather the
    sorted pairs' rows of `x` (and `spare` more: `_spare_rows`), multiply
    each expert's segment by its weights through ``matmul(rows, w, load)``,
    return the results to token order and sum a token's k, weighted by
    the router.  `held`: pairs of experts held elsewhere lie behind every
    segment and add nothing.  Returns out [T, D]."""
    t_len, k = top_w.shape

    def spared(pair_rows):
        return jnp.pad(pair_rows, ((0, spare), (0, 0))) if spare else pair_rows

    ys = expert_ffn(lambda r, w: matmul(r, w, load),
                    spared(x[order // k]), weights,            # [T*k (+), D]
                    None if biases is None
                    else [spared(b.astype(x.dtype)[sorted_e]) for b in biases],
                    act, gated)
    if spare:
        ys = ys[:len(order)]
    if held:
        ys = jnp.where((sorted_e < len(load))[:, None], ys, 0)
    pairs = ys[jnp.argsort(order)].reshape(t_len, k, -1)   # token order
    # on the vector unit: a matmul would round the scores to bfloat16
    return (pairs * top_w.astype(pairs.dtype)[:, :, None]).sum(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_experts(static, interpret, x, order, load, top_w, *weights):
    """`_every_pair` where a program lowered for a TPU runs it as the two
    kernel calls that fetch and place their own rows (`_two_calls`,
    `static` ``(tm, act, gated)`` from `fused_tile`); any other platform,
    and every backward, is `_every_pair` through `lax.ragged_dot`."""
    return lax.platform_dependent(
        x, order, load, top_w, *weights,
        tpu=functools.partial(_two_calls, static, interpret),
        default=functools.partial(_plain_experts, *static[1:]))


def _two_calls(static, interpret, x, order, load, top_w, *weights):
    """`gate_up` reads row r of the sorted pairs at ``x[order[r] // k]``
    and writes ``act(g) * u``; `down` weighs its row r by the router and
    writes it to row ``slot * T + token`` of k slabs of ``[T, D]``, whose
    sum over k is the result — no ``[T k, D]`` copy of `x`, no un-sort, no
    ``[T, k, D]`` relayout.  With no held range every (token, slot) has
    one sorted row: every row of the slabs is written once.  `slab_sum`
    adds them slot 0 first, as `_every_pair` sums a token's k rows."""
    tm, act, _ = static
    t_len, k = top_w.shape
    token = order // k
    call = functools.partial(exported.call, "grouped_matmul_kernel",
                             interpret=interpret)
    h, = call("gate_up", (x, token, load, weights[0]) + weights[2:], tm=tm,
              act=act)
    slabs, = call("down", (h, weights[1], load, order % k * t_len + token,
                           top_w.reshape(-1)[order]),
                  tm=tm, dtype=str(x.dtype))
    # slab on slab where they lie, rows of one sublane, into whole tiles:
    # the kernel's, because XLA's own sum keeps the rows' layout and the
    # loop over a long bucket's pieces then stacks its results in it
    out, = call("slab_sum", (slabs,), k=k)
    return out


def _plain_experts(act, gated, x, order, load, top_w, *weights):
    return _every_pair(_ragged_dot, x, order, None, load, top_w, weights,
                       None, act, gated, 0, False)


def _fused_experts_fwd(static, interpret, *operands):
    return _fused_experts(static, interpret, *operands), operands


def _fused_experts_bwd(static, interpret, operands, cotangent):
    x, order, load, top_w, *weights = operands
    _, vjp = jax.vjp(
        lambda x, top_w, *weights: _plain_experts(
            *static[1:], x, order, load, top_w, *weights),
        x, top_w, *weights)
    d_x, d_top_w, *d_weights = vjp(cotangent)
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (d_x, none(order), none(load), d_top_w, *d_weights)


_fused_experts.defvjp(_fused_experts_fwd, _fused_experts_bwd)


def held_range(held, experts, zero_experts=0):
    """The range `_dropless` walks: `held` ``(first, count)`` or — no range
    named, but `zero_experts` of the router's `experts` columns
    zero-compute — all the real experts, a range of the router's columns
    like any; None where every column is an expert held here."""
    if held is None and zero_experts:
        return 0, experts - zero_experts
    return held


def dropless_experts(x, logits, k, weights, biases=None, act="relu",
                     gated=False, normalize=True, score="softmax",
                     select_bias=None, scale=1.0, held=None, zero_experts=0):
    """`_dropless` (below) of the fewest equal pieces of the tokens whose
    gathered rows — every pair's, or one pass of the held pairs'
    (`_pass_rows`) — are within `_PAIR_BYTES` each — all tokens at once
    where theirs are — one piece after the other (a token's experts do not
    depend on its neighbours): the same numbers, the loads summed."""
    t_len, width = x.shape
    held = held_range(held, logits.shape[-1], zero_experts)
    pieces, _ = pass_plan(t_len, k, width * x.dtype.itemsize, held,
                          logits.shape[-1])
    if pieces == 1:
        return _dropless(x, logits, k, weights, biases, act, gated,
                         normalize, score, select_bias, scale, held,
                         zero_experts)
    out, load = lax.map(
        lambda piece: _dropless(*piece, k, weights, biases, act, gated,
                                normalize, score, select_bias, scale, held,
                                zero_experts),
        (x.reshape(pieces, -1, width),
         logits.reshape(pieces, -1, logits.shape[-1])))
    return out.reshape(t_len, -1), load.sum(0)


def _dropless(x, logits, k, weights, biases=None, act="relu",
              gated=False, normalize=True, score="softmax",
              select_bias=None, scale=1.0, held=None, zero_experts=0):
    """Every token through its k best experts, none dropped.

    x [T, D]; logits [T, E] router scores (softmax here, float32);
    weights (w1 [E, D, H],
    w2 [E, H, D][, w3 [E, D, H]]), biases likewise ([E, H] / [E, D]) or
    None.  The T*k (token, expert) pairs are sorted by expert, each
    expert multiplies its contiguous segment of rows, and the results
    return to token order weighted by the router score (renormalised
    over the k kept when `normalize`).  Returns (out [T, D], load [E]
    — tokens per expert, float32).  Differentiable in x, probs and the
    expert parameters.  What is `width` wide — the gathered rows, the
    three grouped matmuls' outputs, the return to token order — is over
    all T*k pairs; but under `held`, from `_COMPACT_PAIRS` pairs, over
    passes of the held pairs alone (`_pass_rows`, `_held_passes`): only
    the router's top-k, the keys, their sort and `load` stay T*k long.

    `score` ``"sigmoid"`` scores each expert on its own (and renormalises
    with 1e-20 under the sum, as the models that route so do);
    `select_bias [E]` is added for the CHOICE of the k only, the weights
    stay the scores; `scale` multiplies the weights.  `held` ``(first,
    count)`` says WHICH experts the weights are — rows ``first ..
    first + count`` of the E the router scores, one chip's share of an
    expert-parallel layer: the choice and the weights are over all E, the
    pairs whose expert lives elsewhere add nothing here, and `load` is
    ``[count]``, over the experts held.

    `zero_experts` n: the LAST n of the router's E columns are zero-compute
    experts (LongCat-Flash's identity experts) — chosen and weighed with
    the others, they have no matrix and no pair row: a token's ``(sum of
    their weights) * x`` is added where the token lives (scope
    ``mx:moe.zero``), `held` ranges over the ``E - n`` real experts, and
    `load` gains one entry, ``[count + 1]``: the pairs that chose one."""
    t_len, n_exp = logits.shape
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if select_bias is None:
        top_w, top_e = lax.top_k(probs, k)                 # [T, k]
    else:
        _, top_e = lax.top_k(probs + select_bias.astype(jnp.float32), k)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    if normalize:
        total = top_w.sum(-1, keepdims=True)
        top_w = top_w / (total + 1e-20 if score == "sigmoid" else total)
    if scale != 1.0:
        top_w = top_w * scale
    flat_e = top_e.reshape(-1)
    if zero_experts:
        with jax.named_scope("mx:moe.zero"):
            is_zero = top_e >= n_exp - zero_experts
            passed = x * jnp.where(is_zero, top_w, 0).sum(
                -1, keepdims=True).astype(x.dtype)
            zero_pairs = is_zero.sum(dtype=jnp.float32)[None]
    if held is not None:
        # pairs of experts held elsewhere sort behind every segment, at
        # index `count`: no expert multiplies them, and their rows of the
        # result — whatever a segment matmul leaves beyond its segments,
        # zeros on the CPU and stale memory on a TPU — are set to 0
        first, n_exp = held
        flat_e = jnp.where((flat_e >= first) & (flat_e < first + n_exp),
                           flat_e - first, n_exp)
    order = jnp.argsort(flat_e, stable=True)               # pair -> sorted
    sorted_e = flat_e[order]
    load = jnp.zeros((n_exp,), jnp.int32).at[flat_e].add(1, mode="drop")
    rows = _pass_rows(len(order), held, logits.shape[-1])
    out, load = _dropless_real(x, logits.shape[-1], order, sorted_e, load,
                               top_w, rows, weights, biases, act, gated, held)
    if zero_experts:
        out, load = out + passed, jnp.concatenate([load, zero_pairs])
    return out, load


def _dropless_real(x, scored, order, sorted_e, load, top_w, rows, weights,
                   biases, act, gated, held):
    """`_dropless`'s matrices: the sorted pairs, of a router of `scored`
    columns, through the experts that have any.  Returns (out [T, D], load
    float32)."""
    if rows:
        out = _held_passes(x, order, sorted_e, load, top_w, rows, weights,
                           biases, act, gated)
        return out, load.astype(jnp.float32)
    if held is None and biases is None and x.dtype == jnp.float32:
        tm = fused_tile(len(order), *weights[0].shape, gated)
        if tm:
            out = _fused_experts((tm, act, gated), _INTERPRET, x, order, load,
                                 top_w, *weights)
            return out, load.astype(jnp.float32)
    # rows past the last pair lie beyond every segment: no expert multiplies
    # them, nothing reads them, and a gradient that reaches them is cut off
    # with them (`_spare_rows` says why there are any: to steer XLA's tile,
    # so none where the kernel walks its own)
    spare = 0 if kernel_tiles(len(order), *weights[0].shape) else \
        _spare_rows(len(order), scored)
    out = _every_pair(segment_matmul, x, order, sorted_e, load, top_w,
                      weights, biases, act, gated, spare, held is not None)
    return out, load.astype(jnp.float32)


def top_k_gating(logits, k, capacity, normalize=True):
    """Capacity-bounded top-k routing.

    logits: [T, E] router scores.  Returns (dispatch, combine):
      dispatch [T, E, C] one-hot: token t occupies slot c of expert e
      combine  [T, E, C] float:   dispatch * softmax gate weight
    Tokens beyond `capacity` of an expert are dropped (zero combine),
    matching Switch-Transformer semantics; position assignment is by
    token order (deterministic, shape-static).  `normalize`
    renormalises the scores over the experts a token KEEPS.
    """
    t_len, n_exp = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, top_idx = lax.top_k(probs, k)                      # [T, k]
    # mask[t, e] = 1 if e in token t's top-k
    mask = jax.nn.one_hot(top_idx, n_exp, dtype=jnp.float32).sum(1)
    # position of each token within each expert's queue, by token order
    pos = jnp.cumsum(mask, axis=0) * mask - 1.0           # [T, E], -1 if unrouted
    keep = mask * (pos < capacity)
    pos = jnp.where(keep > 0, pos, 0).astype(jnp.int32)
    slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T, E, C]
    dispatch = slot * keep[..., None]
    gates = probs * keep
    if normalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    combine = dispatch * gates[..., None]
    return dispatch, combine


def moe_apply(expert_fn, params, x, gate_w, k=1, capacity_factor=1.0,
              axis_name="expert"):
    """Expert-parallel MoE layer body; call inside `shard_map`.

    params : this shard's expert parameters (leading axis = local expert
             count, usually 1).
    x      : [T_local, D] this shard's tokens.
    gate_w : [D, E] router weight (replicated).
    Dispatch path: gate locally -> all_to_all tokens to expert owners ->
    each shard applies its experts -> all_to_all back -> combine.
    Returns [T_local, D].
    """
    n_shards = axis_size(axis_name)
    t_local, d = x.shape
    local_experts = jax.tree_util.tree_leaves(params)[0].shape[0]
    n_exp = n_shards * local_experts
    capacity = max(1, int(capacity_factor * k * t_local // n_exp))

    dispatch, combine = top_k_gating(router_logits(x, gate_w), k,
                                     capacity)             # [T,E,C]

    # gather expert inputs: [E, C, D] on every shard, then all_to_all so
    # shard s ends up with ITS experts' slots from ALL shards:
    # [E, C, D] -> split E -> [n_shards * local_E, C, D] laid out so the
    # receiving shard concatenates senders along a new leading axis
    exp_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    exp_in = exp_in.reshape(n_shards, local_experts, capacity, d)
    # [S, localE, C, D] --all_to_all--> [S_from, localE, C, D]
    recv = lax.all_to_all(exp_in, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)

    # apply local experts over the concatenated sender axis
    # (per expert: [S_from * C, D] tokens)
    xe = recv.transpose(1, 0, 2, 3).reshape(local_experts,
                                            n_shards * capacity, d)
    ye = jax.vmap(expert_fn)(params, xe.astype(x.dtype))
    ye = ye.reshape(local_experts, n_shards, capacity, d).transpose(1, 0, 2, 3)

    # route results back to the token owners
    back = lax.all_to_all(ye.astype(jnp.float32), axis_name, split_axis=0,
                          concat_axis=0, tiled=False)
    back = back.reshape(n_exp, capacity, d)
    return jnp.einsum("tec,ecd->td", combine, back).astype(x.dtype)


def moe_sharded(mesh, expert_fn, stacked_params, x, gate_w, k=1,
                capacity_factor=1.0, expert_axis="expert", data_axis=None):
    """Host-level expert-parallel apply.

    stacked_params: pytree with leading axis = total experts E (must be a
    multiple of the 'expert' mesh axis size; each shard owns E/n).
    x: [T, D] tokens (sharded over `data_axis` if given, tokens split
    over the expert axis otherwise so all devices participate).
    """
    n_shards = mesh.shape[expert_axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    n_exp = leaves[0].shape[0]
    assert n_exp % n_shards == 0, \
        "experts %d not divisible over %d shards" % (n_exp, n_shards)

    param_spec = jax.tree_util.tree_map(lambda _: P(expert_axis),
                                        stacked_params)
    tok_axes = (data_axis, expert_axis) if data_axis else (expert_axis,)
    tok_spec = P(tok_axes)

    body = functools.partial(moe_apply, expert_fn, k=k,
                             capacity_factor=capacity_factor,
                             axis_name=expert_axis)
    return shard_map_unchecked(
        body,
        mesh=mesh,
        in_specs=(param_spec, tok_spec, P()),
        out_specs=tok_spec,
    )(stacked_params, x, gate_w)


# (What PR 63 adds stands at the END of the file, and `_held_passes` calls it
# from the one line its scatter-add stood on: every line above keeps its
# number, and with it the programs of the layers that hold no range.)
#
# A pass's return to token order is OURS on a TPU (`ops/row_return_kernel.py`)
# wherever the kernel's tiling holds: XLA's scatter-add there is one
# read-modify-write a row whose price goes by the row's WIDTH alone — us a
# row, jaxlib 0.9.0 / libtpu 0.0.34: 4,096 floats 0.3-0.5, 5,120 floats
# 3.7-4.8, 6,144 0.7-1.2, 7,168 1.7-2.0, 8,192 1.0-1.2 (PERF.md section 7,
# builders, PR 57) — where the kernel's is the rows' bytes and one walk of
# the tiles they name.  ONE form for every pass: a pass exists from 1,024
# pairs (`_COMPACT_PAIRS`), and no rule by width, bytes or model chooses.
def return_tiles(t_len, rows, d, dtype):
    """``(tb, tm)``, the tokens a tile and the rows a chunk of
    ``ops.row_return_kernel.row_return`` for a pass's return — `rows`
    weighted rows ``[rows, d]`` of `dtype` added into ``[t_len, d]`` — or
    None where the return is XLA's scatter-add: rows that are no float32,
    a width that is no whole 128-lane tiles, more rows than the TPU's
    scalar memory holds the tokens and the places of (`_FUSED_ROWS`), or
    tiles that three times `_KERNEL_BLOCK` of VMEM do not hold (a width
    past 13,000).  Static shapes in, nothing else:
    `TransformerLM.expert_plan` asks here too."""
    tile = _KERNEL_TILE
    if (jnp.dtype(dtype) != jnp.float32 or d % _LANES or rows > _FUSED_ROWS
            or 7 * tile * d * 4 > 3 * _KERNEL_BLOCK):
        return None
    return tile, tile


def _scatter_add(out, ys, token):
    return out.at[token].add(ys, mode="drop")


def _return_rows(out, ys, token, count):
    """`out [T, D]` with a pass's weighted rows `ys [rows, D]` added to
    their tokens' rows: `token [rows]`, `T` for the rows from `count` on
    (nobody's, and zeros).  One sum with two implementations, as
    `segment_matmul` has: the TPU's kernel, which adds a token's rows in
    the order they lie — ascending expert — after what `out` held, or
    XLA's scatter-add, which is also every platform's backward."""
    tiles = return_tiles(len(out), *ys.shape, ys.dtype)
    if tiles is None or out.dtype != ys.dtype:
        return _scatter_add(out, ys, token)
    return _placed_rows(tiles, _INTERPRET, out, ys, token, count)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _placed_rows(tiles, interpret, out, ys, token, count):
    def kernel(out, ys, token, count):
        # lowered once a shape for all programs and processes
        # (ops/exported.py); `out` is aliased to the result
        out, = exported.call("row_return_kernel", "row_return",
                             (out, ys, token, count), interpret=interpret,
                             tb=tiles[0], tm=tiles[1])
        return out
    return lax.platform_dependent(
        out, ys, token, count, tpu=kernel,
        default=lambda out, ys, token, count: _scatter_add(out, ys, token))


def _placed_rows_fwd(tiles, interpret, out, ys, token, count):
    return _placed_rows(tiles, interpret, out, ys, token, count), (token,
                                                                   count)


def _placed_rows_bwd(tiles, interpret, kept, cotangent):
    # the kernel has no backward: a gradient takes the scatter-add's —
    # `out`'s as it comes, a row's from its token's row (a dead row's: 0)
    token, count = kept
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (cotangent, cotangent.at[token].get(mode="fill", fill_value=0),
            none(token), none(count))


_placed_rows.defvjp(_placed_rows_fwd, _placed_rows_bwd)


# (What PR 64 adds stands here for the same reason.)  An activation an
# UNGATED expert of two matrices takes — ``W2 relu(W1 x)^2``, Nemotron-H's
# `mlp_hidden_act` — joins the two above; `expert_ffn` squares where a gated
# expert multiplies by its second in-projection, and every route of a held
# range and of a whole layer (`_held_passes`, `_every_pair`, `_two_calls`,
# whose up call then reads one matrix an expert) takes `gated=False` as the
# same code with one operand fewer.
def relu2(x):
    """``relu(x)^2``."""
    return jnp.square(jax.nn.relu(x))


ACTIVATIONS["relu2"] = relu2
