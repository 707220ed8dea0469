"""Latent attention (ops/latent.py) over a SUBSET of the cached rows — the
graph-level primitives of the ``"sparse_latent_attention"`` and
``"window_latent_attention"`` layer kinds (models/transformer_lm.py): a
learned selection (DeepSeek-V3.2's indexer) and a sliding window, each over
ONE latent row ``[c (rank) | k_r (rope)]`` a position, with a key width
``nope + rope`` that need not be the value width, a headwise output gate,
and head counts, ranks and rotary bases of the kind's own.

**The indexer.**  `index_heads` J query heads ``q_I (J x D)`` of the query
latent, ONE key ``k_I (D)`` a position (a second cached row, kind
``"index"``, ``(slots, 1, D, ring_len)``), and J weights ``w`` of the
stream: ``I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])``; row t keeps
the `top_k` positions ``s <= t`` of largest ``I[t, s]`` — all of them while
``t < top_k``.  The choice is EXACT over the scores computed: a decode
step's by ``lax.top_k``, a whole sequence's by a bisection on the scores'
bit patterns for each row's `top_k`-th largest (32 counts a row, no sort).
The indexer's products run at the program's precision, JAX's default: on a
TPU one bfloat16 pass with float32 accumulation.

* ``_sparse_latent_attention`` / ``_window_latent_attention`` — the
  UP-PROJECTED form for a whole sequence (training, scoring, prefill), the
  selection (or the window) as a MASK on causal attention, never a gather.
  The node takes the query LATENT and `W_qb`, not the queries: Q, K and V
  are made a GROUP OF HEADS at a time (``_head_group``) and attended a
  block of 512 query positions at a time, so that neither the heads' Q/K/V
  nor a ``(heads, T, T)`` score of a fifteen-thousand-position bucket
  ever exists (a step's K and V are its group's, read whole: the bytes a
  layer reads fall with the block, so the block is large and the group
  small); the selection's ``(T, T)`` mask is made once a layer, a block
  of 128 queries at a time; a long sequence's blocks go in four runs,
  each against the keys up to its own end.  The headwise gate multiplies a
  head's context where it is made.  Scopes ``mx:mla.expand`` (the up-projections),
  ``mx:dsa.index`` (the indexer's scores), ``mx:dsa.select`` (the
  threshold), ``mx:dsa.read`` (the masked attention).
* ``_latent_window_write`` — a prefill's rows into a window layer's latent
  ring of ``min(W, max_len)`` positions: the last W of the prompt's true
  length, each where a decode step would have put it (``position mod W``).
  (A full layer's latent ring and its index keys are written by
  ``_latent_cache_write``.)
* ``_sparse_latent_cached_attention`` — the ABSORBED decode step of a full
  layer: the step's latent row and index key are written at ``length``;
  the indexer scores the row's cached keys; ``lax.top_k`` picks; the
  selected rows are GATHERED from the page and the absorbed attention runs
  over those alone (ops/latent.py has the absorbed form).  ``jax.numpy``
  on every platform — no kernel: on a TPU v5e the whole of it, for four
  rows at ~15k cached positions, is 0.6 ms a layer, 0.5 of them
  ``lax.top_k`` (PERF.md section 6, PR 48).
* ``_window_latent_cached_attention`` — the absorbed step over a ring that
  WRAPS: the row goes to ``length mod W`` and the row attends to the
  ``min(length + 1, W)`` positions that are filled, as
  ``ops.attention._ring_attention`` does for per-head rings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, nn as jnn

from . import attention as _attn
from . import exported
from .registry import register
from .tensor import _lit

_QUERY_BLOCK = 512       # query positions a step of the masked attention
_INDEX_BLOCK = 128       # query positions a step of the indexer
_GROUP_SCORES = 64 << 20  # bytes of a step's scores: they stay on the chip
_GROUP_ROWS = 512 << 20   # bytes of a group's Q, K and V of all positions
_INDEX_HEADS = 8         # indexer heads a product (their scores' bytes)
_RUNS = 4                # runs of query blocks, each against its own keys
_KERNEL_KEYS = 1024      # positions a key block of the TPU's kernel
_KERNEL_HEADS = 4        # heads a grid step of it: they share the mask's block


def _block(t, most):
    """Query positions a step: `most`, or all of a short sequence's in
    whole 64s."""
    return min(most, -(-t // 64) * 64)


def _runs(blocks):
    """A long sequence's query blocks as ``(first, count)`` runs: a run's
    rows see no key beyond the run's own end, so each run's steps take
    the keys up to there and the products above the diagonal that no row
    keeps are mostly not made (four runs spare 3/8 of them)."""
    if blocks < 4 * _RUNS:
        return [(0, blocks)]
    per = -(-blocks // _RUNS)
    return [(b, min(per, blocks - b)) for b in range(0, blocks, per)]


def _head_group(heads, block, keys, head_bytes):
    """Heads attended at a time: the most that divide `heads`, keep a
    step's float32 scores ``(group, block, keys)`` within 64 MiB, so that
    XLA holds them on the chip between the two products, and keep the
    group's Q, K and V of all positions (`head_bytes` a head) within 512
    MiB.  A step reads its group's K and V whole, so the bytes a layer
    reads fall with the BLOCK, not with the group: 512 queries of 2 heads
    read an eighth of what 64 queries of 16 heads do.  Without `keys` no
    score array exists (the TPU's kernel keeps a block's on the chip) and
    the rows alone bound the group."""
    fit = _GROUP_ROWS // head_bytes
    if keys is not None:
        fit = min(fit, _GROUP_SCORES // (4 * block * keys))
    return max(g for g in range(1, heads + 1)
               if heads % g == 0 and g <= max(1, fit))


def masked_block(t, heads, key_dim, value_dim, platform):
    """``(rows, keys)`` — the query positions a grid step of the TPU's
    masked kernel holds (``ops/masked_latent_kernel.py``) and the positions
    of a key block — for a sequence of `t` positions under a selection,
    with `heads` heads whose key is `key_dim` wide (``nope + rope``) beside
    a value of `value_dim`: the body's block of queries and the largest
    multiple of 128 that divides `t` within 1,024 positions a key block
    (on a v5e 512 x 1,024 reads 51.6% of the bfloat16 peak at 15,360
    positions, key blocks of 512 36%: PERF.md section 6, PR 49).  None
    where ``_sparse_latent_attention`` runs its ``jax.numpy`` body
    (``_attend_masked``): off the TPU; where the float32 scores of all
    heads, ``4 H t^2`` bytes, are within the 96 MiB up to which XLA keeps
    them on the chip (``ops.attention._SCORES_ON_CHIP``: for 128 heads a
    `t` under 512 — the tiny rehearsal size, a short scoring call); for a
    `t` that is no multiple of 128, a key width that is no multiple of 64
    or a value width that is no multiple of 128.  Whoever counts what a
    prefill runs (``TransformerLM.call_counters``) asks here, as
    ``ops.attention.prefill_block`` is asked for ``_sdp_attention``."""
    if (platform != "tpu" or t % _attn._LANES or key_dim % 64
            or value_dim % _attn._LANES
            or 4 * heads * t * t <= _attn._SCORES_ON_CHIP):
        return None
    keys = max(b for b in range(_attn._LANES, _KERNEL_KEYS + 1, _attn._LANES)
               if t % b == 0)
    return _block(t, _QUERY_BLOCK), keys


def _pad_rows(x, multiple):
    """``x (T, ...)`` with zero rows appended up to a whole `multiple`."""
    pad = -x.shape[0] % multiple
    return x if not pad else jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


def _kth_largest(scores, k):
    """Each row's `k`-th largest of ``scores (Q, T)`` float32, EXACTLY, by
    bisection on the order-preserving integer image of the floats: the
    largest threshold that at least `k` of the row reach."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    lo = jnp.full(scores.shape[:1], jnp.iinfo(jnp.int32).min, jnp.int32)
    hi = jnp.full(scores.shape[:1], jnp.iinfo(jnp.int32).max, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        # (lo + hi + 1) // 2 without overflow
        mid = (lo >> 1) + (hi >> 1) + ((lo & 1) | (hi & 1))
        enough = jnp.sum(keys >= mid[:, None], axis=1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    lo, _ = lax.fori_loop(0, 32, halve, (lo, hi))
    return keys, lo


def _weighted_relu(scores, weights):
    """``sum_j w_j relu(s_j)``: ``scores (..., J, K)``, ``weights (...,
    J)`` → ``(..., K)``, float32."""
    return jnp.einsum("...h,...hk->...k", weights,
                      jnn.relu(scores)).astype(jnp.float32)


def _split_kv(rows, nope, axis=-1):
    """A head's up-projected channels (or `W_kvb`'s rows of them) as
    ``(k_nope, v)``: the first `nope`, and the rest."""
    return (lax.slice_in_dim(rows, 0, nope, axis=axis),
            lax.slice_in_dim(rows, nope, rows.shape[axis], axis=axis))


def _softmax_scale(nope, rope):
    """A head's key is ``nope + rope`` wide."""
    return (nope + rope) ** -0.5


def _selection(index_q, index_k, index_w, top_k):
    """The indexer's mask ``(T, T)`` bool of one sequence (the rows whole
    blocks: a pad's rows come last): ``index_q (T, J, D)`` and ``index_k
    (T, D)`` rotated, ``index_w (T, J)``."""
    t, heads, dim = index_q.shape
    block = _block(t, _INDEX_BLOCK)
    q, w = _pad_rows(index_q, block), _pad_rows(index_w, block)
    chunk = max(g for g in range(1, min(heads, _INDEX_HEADS) + 1)
                if heads % g == 0)
    keep = []
    for first, count in _runs(q.shape[0] // block):
        seen = min(t, (first + count) * block)    # keys this run's rows see
        keys_of, position = index_k[:seen], jnp.arange(seen)

        def step(at, xs, keys_of=keys_of, position=position, seen=seen):
            q_b, w_b = xs
            with jax.named_scope("mx:dsa.index"):
                score = jnp.zeros((block, seen), jnp.float32)
                for j in range(0, heads, chunk):   # a few heads' at once
                    score = score + _weighted_relu(
                        jnp.einsum("qhd,kd->qhk", q_b[:, j:j + chunk],
                                   keys_of), w_b[:, j:j + chunk])
            with jax.named_scope("mx:dsa.select"):
                causal = position[None, :] <= at + jnp.arange(block)[:, None]
                keys, edge = _kth_largest(
                    jnp.where(causal, score, -jnp.inf), min(top_k, seen))
                return at + block, (keys >= edge[:, None]) & causal

        rows = slice(first * block, (first + count) * block)
        _, kept = lax.scan(step, jnp.int32(first * block), (
            q[rows].reshape(count, block, heads, dim),
            w[rows].reshape(count, block, heads)))
        keep.append(jnp.pad(kept.reshape(count * block, seen),
                            [(0, 0), (0, t - seen)]))
    return jnp.concatenate(keep)


def _attend_masked(q_n, q_r, k_n, k_r, v, keep, scale, block):
    """Queries ``q_n (Tq, G, n)`` / ``q_r (Tq, G, r)`` (``Tq`` whole
    blocks) over keys ``k_n (T, G, n)`` / ``k_r (T, r)`` and values ``v
    (T, G, v)`` under ``keep (Tq, T)``, a block of queries at a time."""
    tq, g, _ = q_n.shape
    t = k_n.shape[0]

    def step(carry, xs, seen):
        qn_b, qr_b, keep_b = xs
        s = (jnp.einsum("qgd,kgd->gqk", qn_b, k_n[:seen])
             + jnp.einsum("qgd,kd->gqk", qr_b, k_r[:seen])) * scale
        p = jnn.softmax(jnp.where(keep_b[None], s, _attn._NEG), axis=-1)
        return carry, jnp.einsum("gqk,kgd->qgd", p, v[:seen])

    ctx = []
    for first, count in _runs(tq // block):
        seen = min(t, (first + count) * block)
        rows = slice(first * block, (first + count) * block)
        _, out = lax.scan(functools.partial(step, seen=seen), 0, (
            q_n[rows].reshape(count, block, g, -1),
            q_r[rows].reshape(count, block, g, -1),
            keep[rows, :seen].reshape(count, block, seen)))
        ctx.append(out.reshape(count * block, g, -1))
    return jnp.concatenate(ctx)


@functools.partial(jax.jit, static_argnames=("scale", "block", "tiled",
                                             "interpret"))
def _masked_read(q_n, q_r, k_n, k_r, v, keep, *, scale, block, tiled,
                 interpret):
    """``_attend_masked`` on whatever platform the program is lowered for:
    the TPU's blockwise kernel (`tiled`: ``masked_block``'s rows and keys,
    and the heads a step; `interpret` runs it in Pallas's interpreter, for
    tests) or the ``jax.numpy`` body.  Jitted, so that the layers of a
    program trace and lower both once.  The kernel has no derivative and needs
    none: under ``jax.grad`` the backward pass is the body's, recomputed
    from the operands."""
    body = functools.partial(_attend_masked, scale=scale, block=block)
    if tiled is None:
        return body(q_n, q_r, k_n, k_r, v, keep)
    rows, keys, heads = tiled

    def kernel(q_n, q_r, k_n, k_r, v, keep):
        # a head's key as wide as its query: the position's one rotary row
        # beside every head's own part
        q = jnp.concatenate([q_n, q_r], axis=-1)
        k = jnp.concatenate([k_n, jnp.broadcast_to(
            k_r[:, None], k_n.shape[:2] + k_r.shape[1:])], axis=-1)
        operands = [x.swapaxes(0, 1) for x in (q, k, v)]
        if not interpret:
            # what one pass of the matrix unit makes of float32 operands,
            # made once (the interpreter on the CPU multiplies in float32,
            # as the CPU's body does)
            operands = [x.astype(jnp.bfloat16) for x in operands]
        # lowered once a shape for all programs and processes
        # (ops/exported.py)
        ctx, = exported.call(
            "masked_latent_kernel", "masked_attention",
            operands + [keep.astype(jnp.int8)], interpret=interpret,
            rows=rows, keys=keys, heads=heads, scale=scale)
        return ctx.swapaxes(0, 1)

    def chosen(*operands):
        return lax.platform_dependent(*operands, tpu=kernel, default=body)

    attend = jax.custom_vjp(chosen)
    attend.defvjp(lambda *operands: (chosen(*operands), operands),
                  lambda operands, g: jax.vjp(body, *operands)[1](g))
    return attend(q_n, q_r, k_n, k_r, v, keep)


def _attend_window(q_n, q_r, k_n, k_r, v, window, scale, block):
    """The same under a sliding `window`: a block of queries reads the
    slab of ``block + window - 1`` positions that ends with it."""
    tq, g, _ = q_n.shape
    blocks = tq // block
    back = window - 1
    slab = block + back

    def front(x):    # `back` rows of pad before position 0, whole blocks after
        return jnp.pad(x, [(back, tq - x.shape[0])]
                       + [(0, 0)] * (x.ndim - 1))

    k_n, k_r, v = front(k_n), front(k_r), front(v)
    # key position less query position, for the slab of block 0
    gap = jnp.arange(slab)[None, :] - back - jnp.arange(block)[:, None]

    def step(first, xs):
        qn_b, qr_b = xs
        kn_b, kr_b, v_b = (lax.dynamic_slice_in_dim(x, first, slab)
                           for x in (k_n, k_r, v))
        s = (jnp.einsum("qgd,kgd->gqk", qn_b, kn_b)
             + jnp.einsum("qgd,kd->gqk", qr_b, kr_b)) * scale
        # the slab's position j is sequence position first - back + j
        keep = (gap <= 0) & (gap > -window) & (
            first - back + jnp.arange(slab)[None, :] >= 0)
        p = jnn.softmax(jnp.where(keep[None], s, _attn._NEG), axis=-1)
        return first + block, jnp.einsum("gqk,kgd->qgd", p, v_b)

    _, ctx = lax.scan(step, jnp.int32(0), (
        q_n.reshape(blocks, block, g, -1), q_r.reshape(blocks, block, g, -1)))
    return ctx.reshape(tq, g, -1)


def _grouped_attention(c_q, qb_weight, latent, kvb_weight, gate, *, heads,
                       rope, value, theta, keep=None, window=None):
    """One sequence's gated context ``(T, H * value)``: ``c_q (T, r_q)``,
    `W_qb` by kind (all heads' q_nope rows, then all heads' q_rope),
    ``latent (T, rank + rope)`` rotated, `W_kvb` head by head ``[k_nope |
    v]``, ``gate (T, H)`` (sigmoid applied) or None; `keep` ``(T, T)`` or a
    `window`."""
    t = c_q.shape[0]
    rank = latent.shape[-1] - rope
    nope = qb_weight.shape[0] // heads - rope
    c, k_r = latent[:, :rank], latent[:, rank:]
    block = _block(t, _QUERY_BLOCK)
    # the tiling a lowering for the TPU would use; which platform the
    # program is lowered for is not known here
    tiled = None if keep is None else masked_block(t, heads, nope + rope,
                                                   value, "tpu")
    keys = None if tiled else t if window is None else block + window
    g = _head_group(heads, block, keys, 4 * t * (2 * nope + rope + value))
    if tiled:   # up to four of the group's heads a grid step
        tiled += (math.gcd(g, _KERNEL_HEADS),)
    groups = heads // g
    qb_n = qb_weight[:heads * nope].reshape(groups, g * nope, -1)
    qb_r = qb_weight[heads * nope:].reshape(groups, g * rope, -1)
    kvb = kvb_weight.reshape(groups, g * (nope + value), -1)
    gates = (jnp.ones((groups, 1, g), c_q.dtype) if gate is None
             else gate.reshape(t, groups, g).transpose(1, 0, 2))
    position = jnp.arange(t)[None, :]
    scale = _softmax_scale(nope, rope)
    if keep is not None:
        keep = _pad_rows(keep, block)

    def group(carry, xs):
        w_n, w_r, w_kv, gate_g = xs
        with jax.named_scope("mx:mla.expand"):
            q_n = (c_q @ w_n.T).reshape(t, g, nope)
            q_r = _attn._rotate((c_q @ w_r.T)[None], position, g,
                                theta)[0].reshape(t, g, rope)
            k_n, v = _split_kv((c @ w_kv.T).reshape(t, g, nope + value),
                               nope)
            q_n, q_r = _pad_rows(q_n, block), _pad_rows(q_r, block)
        with jax.named_scope("mx:dsa.read" if window is None
                             else "mx:attn.window"):
            ctx = (_masked_read(q_n, q_r, k_n, k_r, v, keep, scale=scale,
                                block=block, tiled=tiled,
                                interpret=_attn._INTERPRET)
                   if window is None else
                   _attend_window(q_n, q_r, k_n, k_r, v, window, scale,
                                  block))
        return carry, (ctx[:t] * gate_g[:, :, None]).reshape(t, g * value)

    _, ctx = lax.scan(group, 0, (qb_n, qb_r, kvb, gates))
    return ctx.transpose(1, 0, 2).reshape(t, heads * value)


def _infer_masked(in_shapes, attrs):
    c_q, latent = in_shapes[0], in_shapes[2]
    h = int(_lit(attrs["num_heads"]))
    rope, value = int(_lit(attrs["rope_dim"])), int(_lit(attrs["value_dim"]))
    nope = int(_lit(attrs["nope_dim"]))
    n, t, q_rank = c_q
    ins = [c_q, (h * (nope + rope), q_rank), latent,
           (h * (nope + value), latent[-1] - rope)] + list(in_shapes[4:])
    return ins, [(n, t, h * value)]


def _masked_inputs(attrs):
    names = ["c_q", "qb_weight", "latent", "kvb_weight"]
    names += ["gate"] if attrs.get("gated") else []
    if attrs.get("top_k") is not None:
        names += ["index_q", "index_k", "index_w"]
    return names


def _masked(operands, keep_of, num_heads, rope_dim, value_dim, theta, gated,
            window=None):
    c_q, qb_weight, latent, kvb_weight = operands[:4]
    gate = operands[4] if gated else None
    out = []
    for n in range(c_q.shape[0]):   # a prefill's one sequence; scoring's few
        out.append(_grouped_attention(
            c_q[n], qb_weight, latent[n], kvb_weight,
            None if gate is None else gate[n], heads=num_heads, rope=rope_dim,
            value=value_dim, theta=theta, keep=keep_of(n), window=window))
    return jnp.stack(out)


@register("_sparse_latent_attention",
          inputs=("c_q", "qb_weight", "latent", "kvb_weight", "gate",
                  "index_q", "index_k", "index_w"),
          inputs_for=_masked_inputs, infer_shape=_infer_masked)
def sparse_latent_attention(*operands, num_heads=1, nope_dim=0, rope_dim=0,
                            value_dim=0, theta=10000.0, index_heads=1,
                            top_k=1, gated=False, **kw):
    """Causal latent attention of a whole sequence under the indexer's
    selection, up-projected a group of heads at a time (module
    docstring): ``c_q (N, T, r_q)`` the query latent, `W_qb` by kind,
    ``latent (N, T, rank + rope)`` rows ``[c | k_r]`` rotated, `W_kvb`,
    ``gate (N, T, H)`` where `gated` (its sigmoid taken), ``index_q (N, T,
    J * D)`` and ``index_k (N, T, D)`` rotated, ``index_w (N, T, J)`` →
    gated context ``(N, T, H * value)``.  `theta` turns the queries'
    rotary part here (K's came rotated in `latent`)."""
    h, rope, value, heads_i, top = (int(_lit(v)) for v in (
        num_heads, rope_dim, value_dim, index_heads, top_k))
    index_q, index_k, index_w = operands[-3:]
    t = index_q.shape[1]

    def keep_of(i):
        return _selection(index_q[i].reshape(t, heads_i, -1), index_k[i],
                          index_w[i], top)

    return _masked(operands, keep_of, h, rope, value, float(_lit(theta)),
                   bool(_lit(gated)))


@register("_window_latent_attention",
          inputs=("c_q", "qb_weight", "latent", "kvb_weight", "gate"),
          inputs_for=_masked_inputs, infer_shape=_infer_masked)
def window_latent_attention(*operands, num_heads=1, nope_dim=0, rope_dim=0,
                            value_dim=0, theta=10000.0, window=1, gated=False,
                            **kw):
    """Causal latent attention of a whole sequence under a sliding
    `window` (row t attends ``s <= t`` with ``t - s < window``), operands
    and output as ``_sparse_latent_attention``'s without the indexer's."""
    h, rope, value, w = (int(_lit(v)) for v in (num_heads, rope_dim,
                                                value_dim, window))
    return _masked(operands, lambda i: None, h, rope, value,
                   float(_lit(theta)), bool(_lit(gated)), window=w)


def _infer_window_write(in_shapes, attrs):
    cache, latent, slot, length = in_shapes
    return [cache, latent, slot, slot], [cache]


@register("_latent_window_write",
          inputs=("cache", "latent", "slot", "length"),
          infer_shape=_infer_window_write)
def latent_window_write(cache, latent, slot, length, **kw):
    """Prefill-side fill of a window layer's latent ring ``(slots, 1,
    width, W)``: one request's rows ``(1, T, width)`` into slot ``slot``.
    A ring SHORTER than the bucket takes the last positions of the
    prompt's true `length` n, each where a decode step would have put it:
    ring position r holds the newest ``p < n`` with ``p mod W == r``."""
    slot_i = _attn._as_index(slot).reshape(())
    ring = cache.shape[3]
    if latent.shape[1] > ring:
        r = jnp.arange(ring)
        n = _attn._as_index(length).reshape(())
        newest = r + ring * jnp.maximum((n - 1 - r) // ring, 0)
        latent = jnp.take(latent, newest, axis=1)
    return lax.dynamic_update_slice(
        cache, latent.swapaxes(1, 2)[:, None].astype(cache.dtype),
        (slot_i, 0, 0, 0))


def _absorb(q_nope, q_rope, kvb_weight, heads, value, rank):
    """(absorbed queries ``(B, H, rank + rope)``, `W_kvb`'s value half
    ``(H, value, rank)``, the softmax scale)."""
    b = q_nope.shape[0]
    kvb = kvb_weight.reshape(heads, -1, rank)
    nope = kvb.shape[1] - value
    kvb_k, kvb_v = _split_kv(kvb, nope, axis=1)
    q = jnp.concatenate(
        [jnp.einsum("bhd,hdr->bhr", q_nope.reshape(b, heads, nope), kvb_k),
         q_rope.reshape(b, heads, -1)], axis=-1)
    return q, kvb_v, _softmax_scale(nope, q_rope.shape[-1] // heads)


def _selected_ring_attention(q, new, index_q, index_k, index_w, cache,
                             index_cache, slot_i, len_i, *, rank, scale,
                             top_k):
    """The absorbed step of a full layer in ``jax.numpy``: ``q (B, H,
    width)`` absorbed queries, ``new (B, width)`` the step's latent rows,
    ``index_q (B, J, D)``, ``index_k (B, D)``, ``index_w (B, J)`` →
    ``(context (B, H, rank), cache', index_cache')``.  Rows are written first; then
    each row's indexer scores its page of keys, ``lax.top_k`` keeps the
    `top_k` largest of positions ``0..length`` (all of them while there are
    fewer), and the row attends to the latent rows GATHERED at those
    positions alone."""
    b = q.shape[0]
    ring_len = cache.shape[3]
    ring = _attn._write_rows(cache, new[:, None, :], slot_i, len_i)
    keys = _attn._write_rows(index_cache, index_k[:, None, :], slot_i, len_i)
    with jax.named_scope("mx:dsa.index"):
        scores = jnp.stack([
            _weighted_relu(jnp.einsum("hd,dk->hk", index_q[i],
                                      _attn._page(keys, slot_i[i])[0]),
                           index_w[i]) for i in range(b)])
        scores = jnp.where(jnp.arange(ring_len)[None, :] <= len_i[:, None],
                           scores, -jnp.inf)
    with jax.named_scope("mx:dsa.select"):
        best, chosen = lax.top_k(scores, min(top_k, ring_len))
    with jax.named_scope("mx:dsa.read"):
        rows = jnp.stack([jnp.take(_attn._page(ring, slot_i[i])[0],
                                   chosen[i], axis=1) for i in range(b)])
        s = jnp.einsum("bhw,bwk->bhk", q, rows) * scale
        probs = jnn.softmax(jnp.where((best > -jnp.inf)[:, None, :], s,
                                      _attn._NEG), axis=-1)
        ctx = jnp.einsum("bhk,brk->bhr", probs, rows[:, :rank])
    return ctx, ring, keys


def _infer_sparse_cached(in_shapes, attrs):
    (q_nope, q_rope, latent, kvb, index_q, index_k, index_w, cache,
     index_cache, slot, length) = in_shapes
    h = int(_lit(attrs["num_heads"]))
    value = int(_lit(attrs["value_dim"]))
    b = q_nope[0]
    return ([q_nope, q_rope, latent, kvb, index_q, index_k, index_w, cache,
             index_cache, slot, slot],
            [(b, 1, h * value), cache, index_cache])


@register("_sparse_latent_cached_attention",
          inputs=("q_nope", "q_rope", "latent", "kvb_weight", "index_q",
                  "index_k", "index_w", "cache", "index_cache", "slot",
                  "length"),
          num_outputs=3, infer_shape=_infer_sparse_cached)
def sparse_latent_cached_attention(q_nope, q_rope, latent, kvb_weight,
                                   index_q, index_k, index_w, cache,
                                   index_cache, slot, length, num_heads=1,
                                   rope_dim=0, value_dim=0, index_heads=1,
                                   top_k=1, **kw):
    """One ABSORBED decode step of a full layer under the indexer's
    selection against its latent ring ``(slots, 1, rank + rope, ring_len)``
    and its index keys ``(slots, 1, D, ring_len)`` (slot and length traced
    operands).  ``q_nope (B, 1, H * nope)``, ``q_rope (B, 1, H * rope)``,
    ``latent (B, 1, rank + rope)``, ``index_q (B, 1, J * D)``, ``index_k
    (B, 1, D)`` (all rotated), ``index_w (B, 1, J)`` of the current token.
    Outputs: context ``(B, 1, H * value)`` (ungated), the two updated
    entries."""
    h, rope, value, heads_i, top = (int(_lit(v)) for v in (
        num_heads, rope_dim, value_dim, index_heads, top_k))
    b = q_nope.shape[0]
    rank = latent.shape[-1] - rope
    with jax.named_scope("mx:mla.absorb"):
        q, kvb_v, scale = _absorb(q_nope, q_rope, kvb_weight, h, value, rank)
    u, ring, keys = _selected_ring_attention(
        q, latent.reshape(b, -1), index_q.reshape(b, heads_i, -1),
        index_k.reshape(b, -1), index_w.reshape(b, heads_i), cache,
        index_cache, _attn._as_index(slot), _attn._as_index(length),
        rank=rank, scale=scale, top_k=top)
    with jax.named_scope("mx:mla.absorb"):
        ctx = jnp.einsum("bhr,hvr->bhv", u, kvb_v)
    return ctx.reshape(b, 1, h * value), ring, keys


def _wrapped_ring_attention(q, new, cache, slot_i, len_i, *, rank, scale):
    """The absorbed step against a latent ring that WRAPS, in
    ``jax.numpy``: the row goes to ``length mod W``; once ``length >= W``
    the mask keeps every position, and they are exactly the window's."""
    b = q.shape[0]
    ring_len = cache.shape[3]
    ring = _attn._write_rows(cache, new[:, None, :], slot_i, len_i % ring_len)
    keep = jnp.arange(ring_len)[None, None, :] <= len_i[:, None, None]
    scores = jnp.stack(
        [jnp.einsum("hw,wk->hk", q[i], _attn._page(ring, slot_i[i])[0])
         for i in range(b)]) * scale
    probs = jnn.softmax(jnp.where(keep, scores, _attn._NEG), axis=-1)
    ctx = jnp.stack(
        [jnp.einsum("hk,rk->hr", probs[i],
                    _attn._page(ring, slot_i[i])[0, :rank])
         for i in range(b)])
    return ctx, ring


def _infer_window_cached(in_shapes, attrs):
    q_nope, q_rope, latent, kvb, cache, slot, length = in_shapes
    h = int(_lit(attrs["num_heads"]))
    value = int(_lit(attrs["value_dim"]))
    return ([q_nope, q_rope, latent, kvb, cache, slot, slot],
            [(q_nope[0], 1, h * value), cache])


@register("_window_latent_cached_attention",
          inputs=("q_nope", "q_rope", "latent", "kvb_weight", "cache", "slot",
                  "length"),
          num_outputs=2, infer_shape=_infer_window_cached)
def window_latent_cached_attention(q_nope, q_rope, latent, kvb_weight, cache,
                                   slot, length, num_heads=1, rope_dim=0,
                                   value_dim=0, **kw):
    """One ABSORBED decode step of a window layer against its latent ring
    ``(slots, 1, rank + rope, min(W, max_len))``, operands as
    ``_latent_cached_attention``'s.  Outputs: context ``(B, 1, H *
    value)`` (ungated) and the updated ring."""
    h, rope, value = (int(_lit(v)) for v in (num_heads, rope_dim, value_dim))
    b = q_nope.shape[0]
    rank = latent.shape[-1] - rope
    with jax.named_scope("mx:mla.absorb"):
        q, kvb_v, scale = _absorb(q_nope, q_rope, kvb_weight, h, value, rank)
    with jax.named_scope("mx:mla.ring"), jax.named_scope("mx:attn.window"):
        u, ring = _wrapped_ring_attention(
            q, latent.reshape(b, -1), cache, _attn._as_index(slot),
            _attn._as_index(length), rank=rank, scale=scale)
    with jax.named_scope("mx:mla.absorb"):
        ctx = jnp.einsum("bhr,hvr->bhv", u, kvb_v)
    return ctx.reshape(b, 1, h * value), ring
