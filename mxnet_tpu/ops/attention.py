"""Transformer attention operators — fused multi-head attention for
training/prefill and the slot-indexed KV-cache decode step.

The transformer LM workload (models/transformer_lm.py, ROADMAP item 2)
needs four graph-level primitives beyond the classic registry:

* ``LayerNorm`` — the reference op the zoo lacked (InstanceNorm
  normalizes spatial dims; a transformer normalizes the channel dim).
* ``_sdp_attention`` — fused multi-head scaled-dot-product attention
  over ``(N, T, d_model)`` projected inputs with an optional causal
  mask.  Keeping QK^T -> mask -> softmax -> V inside ONE op keeps the
  symbol graph length-independent (one node per layer, not O(T)), so
  every sequence bucket traces the same graph and only the shapes —
  and therefore the compiled programs — differ.  It returns the
  per-head K/V tensors as extra outputs so the serving prefill graph
  can write them into a KV-cache slot without recomputing the
  projections.
* ``_cached_attention`` / ``_kv_cache_write`` — the decode-side pair.
  The KV ring is one preallocated buffer per layer for K and one for V,
  a PAGE of ``max_len`` positions per slot, stored ``(slots, H_kv,
  d_head, max_len)``: the positions on the minor axis, which on a TPU
  are the lanes (``TransformerLM.cache_spec`` owns the shape — every
  allocator asks it).  The SLOT INDEX and LENGTH ride as traced operands
  (the vLLM/PagedAttention discipline: address pages by index, bound by
  length), so one compiled decode program serves every session mix —
  sessions join/leave between steps without recompiling.  WRITE, THEN
  READ: the step's K/V row goes to ``(slot, :, :, length)`` before the
  row attends to positions ``0..length``, so a token attends to itself.
  A WINDOW layer's ring (the node's `window` W) has ``min(W, max_len)``
  positions for a session that may be longer: the row goes to ``length
  mod W``, a prefill longer than W writes its last W positions where
  decode steps would have put them, and once ``length >= W`` the whole
  ring is read — it holds exactly the window.

  *On a TPU* a decode step's attention is ONE kernel a layer
  (``ops/kv_ring_kernel.py``, Pallas) wherever the ring's shape gives it
  a block (``decode_block``: the largest multiple of 128 positions that
  divides ``max_len`` and keeps ``heads * d_head * block`` floats within
  1 MiB, `heads` being all K/V heads or, for a ring too wide for that,
  ``decode_heads``' group of whole heads).  It reads each packed row's page block by block ONLY AS FAR AS
  the block that holds position ``length`` — the blocks beyond are not
  fetched — with an online softmax over the blocks, puts the new row
  into that last block while it is in fast memory and sends the block
  back to the donated ring by one dense DMA.  *Everywhere else* (the
  CPU, a ring with no block) the ``jax.numpy`` body below runs, and is
  the kernel's oracle: each packed row's K/V is one
  ``dynamic_update_slice`` (in place under donation), each row's
  attention reads its whole page through a ``dynamic_slice`` fused into
  the reduction and masks by length.  ``lax.platform_dependent`` chooses
  when the program is lowered, by the platform it is lowered for; no
  switch, no environment variable.  (A scatter over the two separated
  index axes ``[slot, :, :, length]`` made XLA:TPU convert the WHOLE
  ring between two layouts twice a layer a step: PERF.md section 6,
  PR 26.  With the positions on the lanes a row write is one lane of
  2,048 vectors, which XLA stores one by one — 7 us a row a ring; the
  kernel's block write-back replaced it: PERF.md section 6, PR 32.)

* ``_token_feed`` / ``_greedy_token`` — the sampled token stays on the
  device.  Every serving program ends by taking the greedy token of its
  logits and writing it into ``last_token (slots + 1,)``, a vector
  addressed by slot like the rings and threaded the same way; a decode
  row whose ``data`` is negative reads its token from there.  The host
  can so dispatch step n+1 before it has read step n
  (serving/decode.py).
* ``_draft_feed`` / ``_draft_verify`` / ``_draft_select`` /
  ``_draft_commit`` / ``_draft_start`` — the same for a model that DRAFTS
  (a multi-token-prediction module, `TransformerLM`'s `nextn`): a decode
  step runs two positions a session, the verified token and the draft of
  the one after it, accepts the draft where it is the trunk's own argmax
  and emits one or two tokens; token, draft and POSITION of every slot
  stay on the device in ``last_token (3, slots + 1)`` (scope
  ``mx:mtp.verify``; the end of this file).

The block vocabulary of current open decoders rides beside them:
``RMSNorm`` and the rotary pair ``_rotary`` / ``_rotary_at`` (positions
0..T-1 of a full sequence, or each row's own traced position — the
same split as ``_add_positional`` / ``_add_positional_at``).  Rotary
turns Q and K BEFORE the attention ops see them, so the ring holds
rotated keys and ``_sdp_attention`` / ``_cached_attention`` /
``_kv_cache_write`` are the same for learned and rotary positions.

Everything but the decode step's TPU kernel is pure jnp/lax: the ops
trace into the surrounding XLA executable on CPU and TPU alike (the
blockwise/ring Pallas kernels in parallel/ remain the long-context
training path).
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, nn as jnn

from . import exported
from .registry import register
from .tensor import _bool, _lit

# matches contrib_ops._NEG: a finite mask value keeps softmax rows that
# are ENTIRELY masked (the scratch slot's padded rows) NaN-free
_NEG = -1e30


def _as_index(v):
    """Slot/length operands ride the serving wire as f32 rows (the
    Predictor binds every input float32); index math wants i32."""
    return v.astype(jnp.int32)


# ----------------------------------------------------------------------
# LayerNorm
# ----------------------------------------------------------------------


def _infer_ln(in_shapes, attrs):
    data = in_shapes[0]
    c = (data[-1],)
    return [data, c, c], [data]


@register("LayerNorm", inputs=("data", "gamma", "beta"),
          infer_shape=_infer_ln)
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, **kw):
    """Layer normalization over `axis` (reference src/operator/nn/
    layer_norm-inl.h): normalize, then scale/shift by gamma/beta."""
    axis = int(_lit(axis))
    eps = float(_lit(eps))
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    return (data - mean) * lax.rsqrt(var + eps) * gamma + beta


def _infer_rms(in_shapes, attrs):
    data = in_shapes[0]
    return [data, (data[-1] // int(_lit(attrs.get("num_heads", 1))),)], [data]


@register("RMSNorm", inputs=("data", "gamma"), infer_shape=_infer_rms)
def rms_norm(data, gamma, eps=1e-5, num_heads=1, **kw):
    """Root-mean-square normalization over the last axis with a learned
    gain and no shift (Zhang & Sennrich 2019): ``x / sqrt(mean(x^2) +
    eps) * gamma``, the statistics in float32.  With `num_heads` the last
    axis is that many heads side by side, each normed on its own, and
    `gamma` is ONE head's gain, shared by all."""
    heads = int(_lit(num_heads))
    if heads != 1:
        split = data.shape[:-1] + (heads, data.shape[-1] // heads)
        return rms_norm(data.reshape(split), gamma, eps).reshape(data.shape)
    x = data.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                      + float(_lit(eps)))
    return (x * scale).astype(data.dtype) * gamma


# ----------------------------------------------------------------------
# rotary positions
# ----------------------------------------------------------------------


def _yarn_inv_freq(half, theta, yarn):
    """YaRN's blended frequencies of a rotary part of ``2 * half``
    channels (Peng et al. 2023, "NTK-by-parts"), `yarn` ``(factor,
    original_max, beta_fast, beta_slow)``: pair j turns at ``theta^(-j /
    half)`` where it completes more than `beta_fast` turns over the
    `original_max` positions the model was trained on, at ``1 / factor``
    of that where it completes fewer than `beta_slow`, and at a linear
    blend between — ``f_j = (1 - m_j) * b_j / factor + m_j * b_j`` with
    ``m_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``, ``lo = floor(d(beta_
    fast))``, ``hi = ceil(d(beta_slow))``, ``d(r) = half * ln(original_max /
    (2 pi r)) / ln(theta)``, both clipped to the part's channels."""
    factor, original, fast, slow = (float(v) for v in _lit(yarn))
    dim = 2 * half

    def turns_at(r):
        return dim * math.log(original / (2 * math.pi * r)) / (
            2 * math.log(theta))

    lo = max(math.floor(turns_at(fast)), 0)
    hi = min(math.ceil(turns_at(slow)), dim - 1)
    j = np.arange(half, dtype=np.float64)
    base = theta ** (-j / half)
    keep = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return jnp.asarray((1.0 - keep) * base / factor + keep * base,
                       jnp.float32)


def _rotate(data, positions, num_heads, theta, rotary_dim=None, yarn=None,
            rope_scale=None):
    """Rotate each head of ``data (N, T, d_model)`` by its row's
    ``positions (N, T)``: the rotate-half convention over the WHOLE head
    (pairs ``(i, i + d_head/2)``, angle ``pos * theta^(-2i/d_head)``),
    angles and products in float32.  With `rotary_dim` R the first R
    channels of each head turn so, as a head of R (pairs ``(i, i + R/2)``,
    angle ``pos * theta^(-2i/R)``), and the other ``d_head - R`` pass as
    they are.  With `yarn` the angles' frequencies are YaRN's
    (``_yarn_inv_freq``), and `rope_scale` multiplies cos and sin (its
    attention factor, where that is not 1)."""
    h = int(_lit(num_heads))
    n, t, d = data.shape
    r = d // h if rotary_dim is None else int(_lit(rotary_dim))
    if r != d // h:
        x = data.reshape(n, t, h, d // h)
        turned = _rotate(x[..., :r].reshape(n, t, h * r), positions, h, theta,
                         yarn=yarn, rope_scale=rope_scale)
        return jnp.concatenate([turned.reshape(n, t, h, r), x[..., r:]],
                               axis=-1).reshape(n, t, d)
    half = d // h // 2
    if yarn is None:
        inv_freq = float(_lit(theta)) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = _yarn_inv_freq(half, float(_lit(theta)), yarn)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if rope_scale is not None:
        factor = float(_lit(rope_scale))
        cos, sin = cos * factor, sin * factor
    x = data.astype(jnp.float32).reshape(n, t, h, 2, half)
    x1, x2 = x[:, :, :, 0], x[:, :, :, 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=3)
    return out.reshape(n, t, d).astype(data.dtype)


def _infer_same(in_shapes, attrs):
    return list(in_shapes), [in_shapes[0]]


@register("_rotary", inputs=("data",), infer_shape=_infer_same)
def rotary(data, num_heads=1, theta=10000.0, rotary_dim=None, yarn=None,
           rope_scale=None, **kw):
    """Rotary position embedding of a full sequence ``(N, T, d_model)``:
    row t sits at position t (training / prefill).  `rotary_dim`: the
    leading channels of each head that turn (default: the whole head);
    `yarn` ``(factor, original_max, beta_fast, beta_slow)`` and
    `rope_scale`: YaRN's frequencies and attention factor (``_rotate``)."""
    n, t, _ = data.shape
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (n, t))
    return _rotate(data, pos, num_heads, theta, rotary_dim, yarn, rope_scale)


@register("_rotary_at", inputs=("data", "index"), infer_shape=_infer_same)
def rotary_at(data, index, num_heads=1, theta=10000.0, rotary_dim=None,
              yarn=None, rope_scale=None, **kw):
    """Rotary position embedding where row b's first token sits at
    ``index[b]`` — the decode step's ``length``, a traced operand, so
    one compiled program serves every position.  `rotary_dim`, `yarn`
    and `rope_scale` as ``_rotary``'s."""
    t = data.shape[1]
    pos = _as_index(index)[:, None] + jnp.arange(t)[None, :]
    return _rotate(data, pos, num_heads, theta, rotary_dim, yarn, rope_scale)


# ----------------------------------------------------------------------
# fused multi-head attention (training + prefill)
# ----------------------------------------------------------------------


def _kv_heads(attrs_or_kw, num_heads):
    """`num_kv_heads` of a node: K/V heads, each shared by ``num_heads /
    num_kv_heads`` query heads (grouped-query attention); absent = one
    K/V head a query head."""
    kv = attrs_or_kw.get("num_kv_heads")
    return num_heads if kv is None else int(_lit(kv))


def _scaled(scores, d_head, scale):
    """``q k^T`` times the node's `scale`; without one, divided by
    ``sqrt(d_head)`` as every model before the option was."""
    if scale is None:
        return scores / jnp.sqrt(jnp.asarray(d_head, scores.dtype))
    return scores * _lit(scale)


def _window_scope(window):
    """The device scope ``mx:attn.window`` around a window layer's
    attention (HLO metadata, like ``mx:moe.*``); nothing for a full one."""
    return (contextlib.nullcontext() if window is None
            else jax.named_scope("mx:attn.window"))


def _infer_sdp(in_shapes, attrs):
    q = in_shapes[0]
    num_heads = int(_lit(attrs.get("num_heads", 1)))
    n, t, d = q
    dh = d // num_heads
    kv = _kv_heads(attrs, num_heads)
    heads = (n, kv, t, dh)
    kv_in = (n, t, kv * dh)
    return [q, kv_in, kv_in], [q, heads, heads]


_LANES = 128
# the bytes of a sequence batch's float32 scores, all heads', up to which
# XLA's own form is the shorter program: a v5e has 128 MiB of VMEM, XLA
# keeps scores of 67 and 75 MB there from the first product to the second
# (one fusion a layer at the matrix unit's pace), and scores of 134 MB and
# more go to HBM and back about six times (PERF.md section 6, PR 47, has
# the readings either side)
_SCORES_ON_CHIP = 96 << 20
_PREFILL_ROWS = 1024   # query rows a grid step, all heads of a group
_PREFILL_KEYS = 1024   # positions a key block
_PREFILL_VMEM = 24 << 20


def prefill_block(q_shape, num_heads, kv_heads, platform, causal=True):
    """``(rows, keys)`` — the query positions a grid step of the TPU's
    prefill kernel holds (``ops/sdp_kernel.py``; of every query head of a
    K/V head's group at once) and the positions of a key block — for a
    `query` of `q_shape` ``(N, T, d_model)`` with `num_heads` query heads
    over `kv_heads` K/V heads: the largest multiples of 128 that divide
    ``T`` within 1,024 rows of all the group's heads a step and 1,024
    positions a key block (fewer where a group of sixteen heads' 128 rows
    would not fit beside them).  None where ``_sdp_attention`` runs its
    ``jax.numpy`` body: off the TPU; without the `causal` mask; where the
    float32 scores of all heads, ``4 N H T^2`` bytes, are within 96 MiB
    (XLA keeps them on the chip between its two products, and its one
    fusion a layer is the shorter program: for 30 or 32 heads a ``T`` under
    896, for 16 under 1,280); for a ``T`` that is no multiple of 128 or a
    ``d_head`` that is no multiple of 64; or for a head's whole K and V
    (the pipeline's two buffers each), a step's blocks and its scores
    beyond 24 MiB of the 32 MiB of VMEM the kernel asks for.  Whoever
    counts what a prefill runs (``TransformerLM.call_counters``) asks
    here."""
    n, t, d = q_shape
    d_head = d // num_heads
    group = num_heads // kv_heads
    if (platform != "tpu" or not causal or t % _LANES or d_head % 64
            or 4 * n * num_heads * t * t <= _SCORES_ON_CHIP):
        return None

    def divisor(cap):
        return max(b for b in range(_LANES, max(cap, _LANES) + 1, _LANES)
                   if t % b == 0)

    rows, keys = divisor(_PREFILL_ROWS // group), divisor(_PREFILL_KEYS)
    wide = group * rows
    # bfloat16 operands twice (the pipeline's buffers), the float32
    # output twice, the accumulator, and a block's scores three times
    # over (scores, probabilities, mask)
    held = lambda keys: (2 * 2 * (2 * t + wide) * d_head  # noqa: E731
                         + 3 * 4 * wide * d_head + 3 * 4 * wide * keys)
    # a group so large that 128 rows of all its heads pass `_PREFILL_ROWS`
    # (16 query heads a K/V head: 2,048) takes a shorter key block
    while held(keys) > _PREFILL_VMEM and keys > _LANES:
        keys = divisor(keys - _LANES)
    return (rows, keys) if held(keys) <= _PREFILL_VMEM else None


def prefill_visits(t, block, window=None):
    """The key blocks the TPU's prefill kernel visits for ONE K/V head of
    a sequence of `t` positions tiled by `block` (``prefill_block``'s rows
    and keys), summed over its grid steps — by the kernel's own bounds,
    ``ops/sdp_kernel.py visited``: what the kernel skips, this does not
    count."""
    from .sdp_kernel import visited

    rows, keys = block
    return sum(end - lo for lo, end in (
        visited(first, rows, keys, window) for first in range(0, t, rows)))


def _masked_attention(qg, kh, vh, *, scale, window, causal=True):
    """Attention of ``qg (N, H_kv, r, T, d)`` — each K/V head's group of
    query heads; K and V are never repeated — over ``kh`` / ``vh (N,
    H_kv, T, d)`` in ``jax.numpy``, the scores of all positions at once:
    what runs wherever the TPU's kernel does not, and the kernel's
    oracle."""
    t, dh = qg.shape[-2:]
    scores = _scaled(jnp.einsum("ngrqd,ngkd->ngrqk", qg, kh), dh, scale)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), dtype=bool))
        if window is not None:
            keep &= ~jnp.tril(keep, -window)
        scores = jnp.where(keep, scores, _NEG)
    return jnp.einsum("ngrqk,ngkd->ngrqd", jnn.softmax(scores, axis=-1), vh)


# tests flip this to have `_cached_attention` and `_sdp_attention` run the
# TPU's kernels in Pallas interpret mode on the CPU
_INTERPRET = False


@functools.partial(jax.jit, static_argnames=("scale", "window", "block",
                                             "interpret"))
def _prefill_attention(qg, kh, vh, *, scale, window, block, interpret):
    """A prompt's causal attention on whatever platform the program is
    lowered for: the TPU's blockwise kernel (`block`: ``prefill_block``'s
    rows and keys; `interpret` runs it in Pallas's interpreter, for
    tests) or the ``jax.numpy`` body.  Jitted, so that the layers of a
    program, whose attention is one and the same, trace and lower both
    once.  The kernel has no derivative and needs none: under ``jax.grad``
    (`Module.fit` of a decoder) the backward pass is the body's,
    recomputed from the operands."""
    body = functools.partial(_masked_attention, scale=scale, window=window)
    if block is None:
        return body(qg, kh, vh)
    rows, keys = block

    def kernel(*operands):
        if not interpret:
            # what one pass of the matrix unit makes of float32 operands,
            # made once (the interpreter on the CPU multiplies in float32,
            # as the CPU's body does)
            operands = [x.astype(jnp.bfloat16) for x in operands]
        # lowered once a shape for all programs and processes
        # (ops/exported.py): every prefill and mixed program of a bucket
        # the rule sends here holds this kernel
        with jax.named_scope("mx:attn.prefill"):
            ctx, = exported.call(
                "sdp_kernel", "causal_attention", operands,
                interpret=interpret, rows=rows, keys=keys, window=window,
                scale=(qg.shape[-1] ** -0.5 if scale is None else scale))
        return ctx

    def chosen(*operands):
        return lax.platform_dependent(*operands, tpu=kernel, default=body)

    attend = jax.custom_vjp(chosen)
    attend.defvjp(lambda *operands: (chosen(*operands), operands),
                  lambda operands, g: jax.vjp(body, *operands)[1](g))
    return attend(qg, kh, vh)


@register("_sdp_attention", inputs=("query", "key", "value"),
          num_outputs=3, infer_shape=_infer_sdp)
def sdp_attention(query, key, value, num_heads=1, causal=True, scale=None,
                  window=None, **kw):
    """Fused multi-head scaled-dot-product attention.

    Inputs are the PROJECTED ``(N, T, d_model)`` tensors (the graph
    keeps one FullyConnected for the joint QKV projection); with
    ``num_kv_heads`` < `num_heads` key and value are ``(N, T,
    num_kv_heads * d_head)`` and each K/V head serves ``num_heads /
    num_kv_heads`` consecutive query heads.  `scale` multiplies the
    scores in place of ``1 / sqrt(d_head)``.  With `window` W a causal row
    i attends to ``j <= i`` with ``i - j < W``: itself and the W - 1
    before it.  Outputs:

      0. context ``(N, T, d_model)`` — heads re-merged;
      1. K per K/V head ``(N, H_kv, T, d_head)``;
      2. V per K/V head ``(N, H_kv, T, d_head)``.

    Outputs 1/2 cost nothing (they are the reshapes the op computes
    anyway) and exist for the serving prefill graph, which writes them
    into the session's KV-cache slot (``_kv_cache_write``) so decode
    steps never re-project the prompt.

    *On a TPU* a causal sequence long enough to pay (``prefill_block``)
    goes through ONE blockwise kernel a layer (``ops/sdp_kernel.py``,
    device scope ``mx:attn.prefill``): an online softmax over key blocks
    that visits none above the diagonal or outside the window and writes
    no score to HBM.  *Everywhere else* the scores of all positions are
    made at once (``_masked_attention``)."""
    h = int(_lit(num_heads))
    n, t, d = query.shape
    dh = d // h
    kv = _kv_heads(kw, h)
    causal = _bool(causal)

    def heads(x, count):
        return x.reshape(n, t, count, dh).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(query, h), heads(key, kv), heads(value, kv)
    # query heads grouped over their K/V head (groups of one without
    # `num_kv_heads`)
    qg = qh.reshape(n, kv, h // kv, t, dh)
    scale = None if scale is None else float(_lit(scale))
    window = None if window is None else int(_lit(window))
    with _window_scope(window):
        if causal:
            ctx = _prefill_attention(
                qg, kh, vh, scale=scale, window=window, interpret=_INTERPRET,
                # the tiling a lowering for the TPU would use; which
                # platform the program is lowered for is not known here
                block=prefill_block(query.shape, h, kv, "tpu"))
        else:
            ctx = _masked_attention(qg, kh, vh, scale=scale, window=None,
                                    causal=False)
    ctx = ctx.reshape(n, h, t, dh)
    return ctx.transpose(0, 2, 1, 3).reshape(n, t, d), kh, vh


# ----------------------------------------------------------------------
# KV-cache decode step
# ----------------------------------------------------------------------


def _infer_cached(in_shapes, attrs):
    q, k, v, kc, vc, slot, length = in_shapes
    num_heads = int(_lit(attrs.get("num_heads", 1)))
    kv = _kv_heads(attrs, num_heads)
    kv_in = q if kv == num_heads else (q[0], q[1], q[2] // num_heads * kv)
    return [q, kv_in, kv_in, kc, kc, slot, slot], [q, kc, kc]


_BLOCK_BYTES = 1 << 20


def decode_heads(ring_shape, itemsize=4, latent=False):
    """K/V heads of a ring ``(slots, H_kv, d_head, max_len)`` that one
    block of the TPU kernel holds: ALL of them wherever 128 positions of
    all heads are within 1 MiB; for a wider ring the most heads that
    divide ``H_kv``, fit, and fill whole tiles of 128 lines (heads are
    independent in a decode step; the kernel's grid walks the groups).
    None for a ring the kernel's tiling does not divide: a ``d_head``
    under 8, or that neither divides 128 (several heads a tile of 128
    lines) nor is a multiple of it (a head over several tiles), or no such
    group of heads.  A `latent` ring ``(slots, 1, width, ring_len)`` is
    ONE page for all query heads (ops/latent_ring_kernel.py reads it by
    two matrix products): 1 wherever its `width` is whole 8-line tiles
    and 128 positions of it are within 1 MiB."""
    _, h_kv, d_head, _ = ring_shape
    if latent:
        fits = d_head % 8 == 0 and d_head * _LANES * itemsize <= _BLOCK_BYTES
        return 1 if h_kv == 1 and fits else None
    if d_head < 8 or (_LANES % d_head and d_head % _LANES):
        return None
    return next((h for h in range(h_kv, 0, -1)
                 if h_kv % h == 0 and h * d_head % _LANES == 0
                 and h * d_head * _LANES * itemsize <= _BLOCK_BYTES), None)


def decode_block(ring_shape, platform, itemsize=4, latent=False):
    """Positions of a page that one step of the TPU kernel holds in fast
    memory, for a ring ``(slots, H_kv, d_head, max_len)``: the largest
    multiple of 128 that divides ``max_len`` and keeps a block
    ``(decode_heads, d_head, block)`` within 1 MiB.  None where
    ``_cached_attention`` (for a `latent` ring ``_latent_cached_
    attention``) runs its ``jax.numpy`` body and reads whole
    pages: off the TPU, or for a ring the kernel's tiling does not divide
    (``max_len`` not a multiple of 128; no ``decode_heads``).  The decode
    program reads ``length // block + 1`` blocks of a row's page —
    whoever counts what a step reads (serving/decode.py) asks here."""
    _, _, d_head, max_len = ring_shape
    heads = decode_heads(ring_shape, itemsize, latent)
    if platform != "tpu" or heads is None or max_len % _LANES:
        return None
    return max(blk for blk in range(_LANES, max_len + 1, _LANES)
               if max_len % blk == 0
               and heads * d_head * blk * itemsize <= _BLOCK_BYTES)


def _page(cache, slot_i):
    """``cache[slot_i]`` — one slot's page ``(H, d_head, max_len)`` as a
    dynamic slice: fused into the reduction that reads it, so the page
    is read where it lies and never copied out."""
    return lax.dynamic_index_in_dim(cache, slot_i, 0, keepdims=False)


def _write_rows(cache, rows, slot_i, len_i):
    """``cache[slot_i[b], :, :, len_i[b]] = rows[b]`` (`len_i`: the ring
    position, which a window ring's caller has wrapped) for every packed
    row, one ``dynamic_update_slice`` each, in row order: XLA keeps the
    ring's layout and — the serve program donates the rings — writes the
    donated buffer in place."""
    for b in range(rows.shape[0]):
        cache = lax.dynamic_update_slice(
            cache, rows[b][None, :, :, None], (slot_i[b], 0, 0, len_i[b]))
    return cache


def _ring_attention(q, k_new, v_new, k_cache, v_cache, slot_i, len_i,
                    scale=None, wraps=False):
    """The decode step against the rings in ``jax.numpy``: what runs
    wherever the TPU kernel does not, and the kernel's oracle.  ``q (B,
    H_q, d)``, ``k_new`` / ``v_new (B, H_kv, d)`` → ``(context (B, H_q,
    d), k_cache', v_cache')``.  Rows are written first, then each row
    reads its whole page and masks by length.  A ring that `wraps` (a
    window layer's: W positions for a session that may be longer) takes
    the row at ``length mod W``, over the oldest position it held; once
    ``length >= W`` the mask keeps every position, and they are exactly
    the window's."""
    b, h, dh = q.shape
    kv = k_new.shape[1]
    max_len = k_cache.shape[3]
    at = len_i % max_len if wraps else len_i
    kc = _write_rows(k_cache, k_new, slot_i, at)
    vc = _write_rows(v_cache, v_new, slot_i, at)
    keep = jnp.arange(max_len)[None, None, :] <= len_i[:, None, None]
    # each ring head read once, by its group of query heads (groups of
    # one are plain multi-head attention)
    qg = q.reshape(b, kv, h // kv, dh)
    scores = _scaled(jnp.stack(
        [jnp.einsum("grd,gdk->grk", qg[i], _page(kc, slot_i[i]))
         for i in range(b)]), dh, scale).reshape(b, h, max_len)
    probs = jnn.softmax(jnp.where(keep, scores, _NEG), axis=-1)
    probs = probs.reshape(b, kv, h // kv, max_len)
    ctx = jnp.stack(
        [jnp.einsum("grk,gdk->grd", probs[i], _page(vc, slot_i[i]))
         for i in range(b)])
    return ctx.reshape(b, h, dh), kc, vc


@functools.partial(jax.jit, static_argnames=("block", "heads", "scale",
                                             "interpret", "wraps"))
def _decode_attention(q, k_new, v_new, k_cache, v_cache, slot_i, len_i, *,
                      block, heads, scale, interpret, wraps=False):
    """The decode step against the rings on whatever platform the
    program is lowered for: the TPU's kernel (with `block` positions of
    `heads` K/V heads a step; `interpret` runs it in Pallas's
    interpreter, for tests) or the
    ``jax.numpy`` body.  Jitted, so that the layers of a decode program,
    whose attention is one and the same, trace and lower both once."""
    operands = (q, k_new, v_new, k_cache, v_cache, slot_i, len_i)
    body = functools.partial(_ring_attention, scale=scale, wraps=wraps)
    if block is None:
        return body(*operands)

    def kernel(*operands):
        # lowered once a shape for all programs and processes
        # (ops/exported.py): a session's decode ladder and, since PR 46,
        # every prefill bucket's mixed step hold this kernel
        return exported.call(
            "kv_ring_kernel", "ring_attention", operands,
            interpret=interpret, block=block, heads=heads, scale=scale,
            wraps=wraps)
    return lax.platform_dependent(*operands, tpu=kernel, default=body)


@register("_cached_attention",
          inputs=("query", "key", "value", "k_cache", "v_cache", "slot",
                  "length"),
          num_outputs=3, infer_shape=_infer_cached)
def cached_attention(query, key, value, k_cache, v_cache, slot, length,
                     num_heads=1, scale=None, window=None, **kw):
    """One decode step of multi-head attention against a slot-indexed
    KV ring (the PagedAttention shape: address each session's page by
    slot index, bound by length — both TRACED operands, so one compiled
    program serves any session mix).

    query/key/value: ``(B, 1, d_model)`` projections of the current
    token (key/value ``(B, 1, num_kv_heads * d_head)`` under
    grouped-query attention); ``k_cache``/``v_cache``: rings as the
    model's ``cache_spec`` states them, ``(slots, H_kv, d_head,
    max_len)``; ``slot``/``length``: ``(B,)`` — session slot index and the
    number of tokens already cached (== the new token's position).
    `scale` multiplies the scores in place of ``1 / sqrt(d_head)``.

    The step's K/V are written at ``cache[slot, :, :, length]`` FIRST,
    then each row attends over its own page ``cache[slot, :, :,
    :length+1]``, so the new token attends to itself like the
    full-sequence forward.  A row reads only its own page, in place: the
    step's ring traffic is B pages at most, whatever the number of
    slots — and on a TPU (module docstring) only the blocks of each page
    up to the one that holds `length`.  Padded rows of a partial decode
    batch point at the ring's scratch slot with length 0: their writes
    land one after the other on its position 0 and their softmax stays
    finite — garbage nobody reads.

    With `window` W the ring is a window layer's: ``min(W, max_len)``
    positions for a session that may be longer.  The step's row goes to
    position ``length mod W`` and the row attends to the ``min(length +
    1, W)`` positions that are filled — the window ``i - j < W`` and
    nothing else, because the row it overwrote was the one that had just
    left it.

    Outputs: context ``(B, 1, d_model)``, updated k_cache, updated
    v_cache (functional update — the serving session threads the rings
    through every call; on TPU the donated-input path makes the update
    in place)."""
    h = int(_lit(num_heads))
    b, one, d = query.shape
    dh = d // h
    kv = _kv_heads(kw, h)
    scale = None if scale is None else float(_lit(scale))
    with _window_scope(window):
        ctx, kc, vc = _decode_attention(
            query.reshape(b, h, dh), key.reshape(b, kv, dh),
            value.reshape(b, kv, dh), k_cache, v_cache, _as_index(slot),
            _as_index(length), scale=scale, interpret=_INTERPRET,
            wraps=window is not None,
            # the block a lowering for the TPU would use; which platform
            # the program is lowered for is not known here
            block=decode_block(k_cache.shape, "tpu", k_cache.dtype.itemsize),
            heads=decode_heads(k_cache.shape, k_cache.dtype.itemsize))
    return ctx.reshape(b, 1, d), kc, vc


def _kv_write_inputs(attrs):
    """A window ring's write also takes the prompt's true `length`."""
    names = ["k_cache", "v_cache", "k_block", "v_block", "slot"]
    return names + ["length"] if attrs.get("window") is not None else names


def _infer_kv_write(in_shapes, attrs):
    kc, vc, kb, vb, slot = in_shapes[:5]
    return [kc, kc, kb, kb] + [slot] * (len(in_shapes) - 4), [kc, kc]


@register("_kv_cache_write",
          inputs=("k_cache", "v_cache", "k_block", "v_block", "slot"),
          inputs_for=_kv_write_inputs,
          num_outputs=2, infer_shape=_infer_kv_write)
def kv_cache_write(k_cache, v_cache, k_block, v_block, slot, length=None,
                   window=None, **kw):
    """Prefill-side cache fill: write one request's per-head K/V block
    ``(1, H_kv, T, d_head)`` into ring slot ``slot`` at positions
    ``[0, T)``, turned to the ring's stored order ``(H_kv, d_head, T)``.
    Positions beyond the request's true length hold
    garbage from the padded prefill — safe by construction: decode
    masks by length and OVERWRITES position `length` before the mask
    ever exposes it.

    A `window` layer's ring may be SHORTER than the bucket.  It then
    takes the last positions of the prompt's true `length` n, each where
    a decode step would have put it: ring position r holds the newest
    ``p < n`` with ``p mod W == r``."""
    slot_i = _as_index(slot).reshape(())
    start = (slot_i, 0, 0, 0)
    ring = k_cache.shape[3]
    if window is not None and k_block.shape[2] > ring:
        r = jnp.arange(ring)
        n = _as_index(length).reshape(())
        newest = r + ring * jnp.maximum((n - 1 - r) // ring, 0)
        k_block, v_block = (jnp.take(block, newest, axis=2)
                            for block in (k_block, v_block))
    return (lax.dynamic_update_slice(k_cache, k_block.swapaxes(2, 3), start),
            lax.dynamic_update_slice(v_cache, v_block.swapaxes(2, 3), start))


# ----------------------------------------------------------------------
# positional embedding add
# ----------------------------------------------------------------------


def _infer_pos(in_shapes, attrs):
    data = in_shapes[0]
    return list(in_shapes), [data]


@register("_add_positional", inputs=("data", "pos_weight"),
          infer_shape=_infer_pos)
def add_positional(data, pos_weight, **kw):
    """``data (N, T, d) + pos_weight[:T]`` — learned positional
    embedding for the full-sequence (training / prefill) forward.  The
    slice length is the traced shape, so every sequence bucket shares
    this one graph node."""
    t = data.shape[1]
    return data + pos_weight[None, :t, :]


@register("_add_positional_at", inputs=("data", "pos_weight", "index"),
          infer_shape=_infer_pos)
def add_positional_at(data, pos_weight, index, **kw):
    """``data (B, 1, d) + pos_weight[index]`` per row — the decode-step
    positional add, where each session sits at its OWN position
    (``index`` == the session length, a traced operand)."""
    idx = _as_index(index)
    return data + pos_weight[idx][:, None, :]


def _infer_take_step(in_shapes, attrs):
    data, index = in_shapes
    n, t, d = data
    return [data, index], [(n, d)]


@register("_take_step", inputs=("data", "index"),
          infer_shape=_infer_take_step)
def take_step(data, index, **kw):
    """``data[i, index[i]]`` for each batch row — prefill uses it to
    pick the LAST VALID position's hidden state (``index = length-1``)
    out of the padded sequence bucket, so the next-token logits come
    from the request's true tail, not the pad."""
    idx = _as_index(index)
    return data[jnp.arange(data.shape[0]), idx]


# ----------------------------------------------------------------------
# the sampled token, kept on the device between steps
# ----------------------------------------------------------------------


def _infer_token_feed(in_shapes, attrs):
    data, last_token, slot = in_shapes
    return [data, last_token, slot], [data]


@register("_token_feed", inputs=("data", "last_token", "slot"),
          infer_shape=_infer_token_feed)
def token_feed(data, last_token, slot, **kw):
    """The token ids ``(B, 1)`` a decode step embeds: ``data`` where the
    host knew the token when it packed the row, and ``last_token[slot]``
    — what the slot's previous program sampled — where it wrote a
    negative number because that token was still in flight."""
    return jnp.where(data < 0, last_token[_as_index(slot)][:, None], data)


def _infer_greedy(in_shapes, attrs):
    logits, last_token, slot = in_shapes
    rows = (logits[0],)
    return [logits, last_token, rows], [rows, last_token]


@register("_greedy_token", inputs=("logits", "last_token", "slot"),
          num_outputs=2, infer_shape=_infer_greedy)
def greedy_token(logits, last_token, slot, **kw):
    """Greedy sampling where the logits are: ``argmax`` of each row of
    ``logits (B, vocab)``, the first index on a tie as ``numpy.argmax``
    has it, in the wire's float32.  Outputs the tokens ``(B,)`` and
    ``last_token`` with ``last_token[slot[b]] = token[b]`` written in row
    order (padded rows all land on the scratch slot)."""
    token = jnp.argmax(logits, axis=-1).astype(last_token.dtype)
    slot_i = _as_index(slot)
    for b in range(token.shape[0]):
        last_token = lax.dynamic_update_slice(
            last_token, token[b:b + 1], (slot_i[b],))
    return token, last_token


# ----------------------------------------------------------------------
# a DRAFT beside the sampled token: the model's own multi-token-
# prediction module guesses the token after the one just sampled, and the
# next step VERIFIES the guess — two positions a row, one or two tokens
# ----------------------------------------------------------------------
#
# A drafting model threads ``last_token (3, slots + 1)`` where the others
# thread ``(slots + 1,)``: row 0 each slot's last verified token (sampled,
# not yet through the trunk), row 1 the draft of the token after it, row 2
# the positions the slot has cached.  The row's POSITION is on the device
# because it depends on whether the device accepted the last draft, which
# the host, a step behind, does not know yet.


def _infer_draft_feed(in_shapes, attrs):
    data, length, last_token, slot = in_shapes
    b = data[0]
    return ([data, (b,), last_token, (b,)],
            [(2 * b, 1), (2 * b,), (2 * b,)])


@register("_draft_feed", inputs=("data", "length", "last_token", "slot"),
          num_outputs=3, infer_shape=_infer_draft_feed)
def draft_feed(data, length, last_token, slot, **kw):
    """What a drafting decode step runs: each session's TWO rows.  ``data
    (B, 1)`` and ``length (B,)`` are the host's — the last token and the
    positions cached — or, where the host wrote a negative `data` because
    the step before is still in flight, ``last_token[0 | 2, slot]``; the
    draft is ``last_token[1, slot]`` always (the host never reads one).
    Outputs ``tokens (2B, 1)``, ``slot (2B,)``, ``length (2B,)``: rows
    ``0..B-1`` the sessions' verified tokens at their positions n, rows
    ``B..2B-1`` their drafts at n + 1, in the wire's float32."""
    slot_i = _as_index(slot)
    unread = data[:, 0] < 0
    token = jnp.where(unread, last_token[0, slot_i], data[:, 0])
    n = jnp.where(unread, last_token[2, slot_i], length)
    return (jnp.concatenate([token, last_token[1, slot_i]])[:, None],
            jnp.concatenate([slot, slot]), jnp.concatenate([n, n + 1]))


def _infer_draft_verify(in_shapes, attrs):
    logits, fed = in_shapes
    rows = logits[0]
    return [logits, (rows, 1)], [(rows, 1), (rows // 2,)]


@register("_draft_verify", inputs=("logits", "fed"), num_outputs=2,
          infer_shape=_infer_draft_verify)
def draft_verify(logits, fed, **kw):
    """THE VERIFY RULE: ``logits (2B, vocab)`` of the rows ``_draft_feed``
    made, `fed` the tokens they ran on.  ``a = argmax logits[:B]`` is the
    trunk's token after each session's verified one; its draft ``fed[B:]``
    is ACCEPTED where it equals `a`, and then ``b = argmax logits[B:]`` is
    the trunk's token after the draft.  Outputs ``sampled (2B, 1)`` = ``[a;
    b]`` (what the draft module embeds beside each row's stream) and
    ``accept (B,)`` in {0, 1}.  Nothing here or after it overrides the
    comparison: a session receives the trunk's own greedy tokens."""
    with jax.named_scope("mx:mtp.verify"):
        b = logits.shape[0] // 2
        sampled = jnp.argmax(logits, axis=-1).astype(fed.dtype)
        accept = (sampled[:b] == fed[b:, 0]).astype(fed.dtype)
        return sampled[:, None], accept


def _infer_draft_select(in_shapes, attrs):
    z, accept = in_shapes
    rows, _, d = z
    return [z, (rows // 2,)], [(rows // 2, d)]


@register("_draft_select", inputs=("data", "accept"),
          infer_shape=_infer_draft_select)
def draft_select(data, accept, **kw):
    """Each session's LAST VALID row of the draft module's output ``data
    (2B, 1, d)``: its second where the draft was accepted, else its
    first."""
    b = data.shape[0] // 2
    return jnp.where(accept[:, None] > 0, data[b:, 0], data[:b, 0])


def _infer_draft_commit(in_shapes, attrs):
    sampled, accept, logits, length, last_token, slot = in_shapes
    b = accept[0]
    return ([(2 * b, 1), (b,), logits, (2 * b,), last_token, (b,)],
            [(b, 3), last_token])


@register("_draft_commit",
          inputs=("sampled", "accept", "draft_logits", "length",
                  "last_token", "slot"),
          num_outputs=2, infer_shape=_infer_draft_commit)
def draft_commit(sampled, accept, draft_logits, length, last_token, slot,
                 **kw):
    """What a drafting step leaves: ``token (B, 3)`` = ``[count, a, b]``
    for the host (count 1 or 2 tokens emitted; `b` is the session's only
    where count is 2) and ``last_token`` with, at each row's slot in row
    order, the last verified token (`b` if accepted else `a`), the NEXT
    draft (``argmax draft_logits``) and the position ``n + count``:
    a rejected draft's row of every ring lies at ``n + 1``, where the next
    step writes before it reads."""
    with jax.named_scope("mx:mtp.verify"):
        b = accept.shape[0]
        first, second = sampled[:b, 0], sampled[b:, 0]
        count = 1 + accept
        state = jnp.stack([
            jnp.where(accept > 0, second, first),
            jnp.argmax(draft_logits, axis=-1).astype(last_token.dtype),
            length[:b] + count]).astype(last_token.dtype)
        slot_i = _as_index(slot)
        for r in range(b):
            last_token = lax.dynamic_update_slice(
                last_token, state[:, r:r + 1], (0, slot_i[r]))
        return jnp.stack([count, first, second], axis=1), last_token


def _infer_draft_shift(in_shapes, attrs):
    data, logits, length = in_shapes
    return [data, logits, (data[0],)], [data]


@register("_draft_shift", inputs=("data", "logits", "length"),
          infer_shape=_infer_draft_shift)
def draft_shift(data, logits, length, **kw):
    """The tokens that FOLLOW a prompt's positions, what a prefill's draft
    module embeds: ``data (1, T)`` shifted left by one, with the first
    sampled token (``argmax logits (1, vocab)``) after the prompt's last
    (position ``length - 1``); the pad's rows are nobody's."""
    first = jnp.argmax(logits, axis=-1).astype(data.dtype)
    at = jnp.arange(data.shape[1])[None, :]
    return jnp.where(at == _as_index(length)[:, None] - 1, first[:, None],
                     jnp.roll(data, -1, axis=1))


def _infer_draft_start(in_shapes, attrs):
    logits, draft_logits, length, last_token, slot = in_shapes
    return ([logits, draft_logits, (1,), last_token, (1,)],
            [(1, 3), last_token])


@register("_draft_start",
          inputs=("logits", "draft_logits", "length", "last_token", "slot"),
          num_outputs=2, infer_shape=_infer_draft_start)
def draft_start(logits, draft_logits, length, last_token, slot, **kw):
    """A PREFILL's end for a drafting model: the first token (``argmax
    logits (1, vocab)``), the first draft (``argmax draft_logits``) and
    the prompt's `length` go to the slot's column of ``last_token``;
    ``token (1, 3)`` = ``[1, first, first]``."""
    with jax.named_scope("mx:mtp.verify"):
        first = jnp.argmax(logits, axis=-1).astype(last_token.dtype)
        draft = jnp.argmax(draft_logits, axis=-1).astype(last_token.dtype)
        state = jnp.stack([first, draft, length.astype(last_token.dtype)])
        last_token = lax.dynamic_update_slice(
            last_token, state, (0, _as_index(slot)[0]))
        return jnp.stack([jnp.ones_like(first), first, first], axis=1), \
            last_token
