"""Latent attention (MLA: DeepSeek-V2/V3's, Mistral-Small-4's) — the
three graph-level primitives of the ``"latent_attention"`` layer kind
(models/transformer_lm.py).

A position's K and V are never stored per head.  What the layer keeps is
ONE row a position for all heads, ``[c (rank) | k_r (rope)]``: the normed
joint down-projection ``c`` and the one rotary key ``k_r`` every head
shares, already rotated.  A head's key is ``[c W_kvb,h[:, :nope] | k_r]``
and its value ``c W_kvb,h[:, nope:]``, `W_kvb` the per-head up-projection
``(H * (nope + value), rank)``.

* ``_latent_attention`` — the UP-PROJECTED form, for a whole sequence
  (training, scoring, prefill): K and V are made from the rows by `W_kvb`
  and go through ``ops.attention.sdp_attention`` (whose heads have ONE
  width: where a head's value is narrower than its key of ``nope + rope``,
  the value rides at the key's width, zeros behind it, and the context's
  first `value` channels are kept — the same numbers; `scale` carries the
  model's softmax scale).  Device scope ``mx:mla.expand``.
* ``_latent_cache_write`` — the prefill's rows into the latent ring
  ``(slots, 1, rank + rope, ring_len)``: positions on the minor axis like
  every ring, addressed by slot, masked by length.
* ``_latent_cached_attention`` — the ABSORBED decode step: `W_kvb`'s key
  half is folded into the query (``qa_h = q_nope_h W_kvb,h[:, :nope]^T``,
  `rank` wide) and its value half is applied to the context after the
  softmax (``ctx_h = u_h W_kvb,h[:, nope:]``), so that scores and context
  are products with the stored rows themselves: ``score_h[s] = scale *
  (qa_h . c[s] + q_rope_h . k_r[s])`` against all ``rank + rope`` lines of
  a row, ``u_h = sum_s p_h[s] c[s]`` from its first `rank` — K and V are
  ONE buffer.  The same numbers as the up-projected form (the products
  are re-associated).  Scopes ``mx:mla.absorb`` (the two absorbed
  products) and ``mx:mla.ring`` (the page read).

  *On a TPU* the page read is ONE kernel a layer
  (``ops/latent_ring_kernel.py``) wherever the ring has a block
  (``ops.attention.decode_block(..., latent=True)``): each packed row's
  page is read once, block by block only as far as the block that holds
  ``length``, and the new row is written where that block lies.
  *Everywhere else* the ``jax.numpy`` body below runs, and is the
  kernel's oracle.  ``lax.platform_dependent`` chooses when the program is
  lowered; no switch, no environment variable.

`query_scale` ``(beta, period)`` on either attention node multiplies the
query at position p by ``1 + beta * ln(1 + floor(p / period))`` — exactly
1 below `period` (Mistral's ``llama_4_scaling_beta``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, nn as jnn

from . import attention as _attn
from . import exported
from .registry import register
from .tensor import _lit


def _query_factor(positions, query_scale):
    """``1 + beta * ln(1 + floor(p / period))`` of each position."""
    beta, period = (float(v) for v in _lit(query_scale))
    return 1.0 + beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / period))


def _dims(attrs, q_nope, latent):
    """(heads, nope, rope, rank, value) of a node from its operands."""
    h = int(_lit(attrs.get("num_heads", 1)))
    value = int(_lit(attrs["value_dim"]))
    rope = int(_lit(attrs["rope_dim"]))
    return h, q_nope[-1] // h, rope, latent[-1] - rope, value


def _infer_latent(in_shapes, attrs):
    q_nope, q_rope, latent, kvb = in_shapes
    h, nope, rope, rank, value = _dims(attrs, q_nope, latent)
    n, t, _ = q_nope
    return ([q_nope, (n, t, h * rope), latent, (h * (nope + value), rank)],
            [(n, t, h * value)])


@register("_latent_attention",
          inputs=("q_nope", "q_rope", "latent", "kvb_weight"),
          infer_shape=_infer_latent)
def latent_attention(q_nope, q_rope, latent, kvb_weight, num_heads=1,
                     rope_dim=0, value_dim=0, scale=None, query_scale=None,
                     **kw):
    """Causal latent attention over a whole sequence, up-projected:
    ``q_nope (N, T, H * nope)``, ``q_rope (N, T, H * rope)`` (rotated),
    ``latent (N, T, rank + rope)`` rows ``[c | k_r]`` (normed; rotated),
    ``kvb_weight (H * (nope + value), rank)`` → context ``(N, T, H *
    value)``.  Each head's key ``[c W_k,h | k_r]`` and value ``c W_v,h``
    go through ``sdp_attention``, a `value_dim` under ``nope + rope``
    padded to it with zeros that the context then drops (the models' graph
    builder checks that it is not over)."""
    h, rope, value = (int(_lit(v)) for v in (num_heads, rope_dim, value_dim))
    n, t, _ = q_nope.shape
    rank = latent.shape[-1] - rope
    with jax.named_scope("mx:mla.expand"):
        c, k_r = latent[..., :rank], latent[..., rank:]
        kv = jnp.einsum("ntr,fr->ntf", c, kvb_weight).reshape(n, t, h, -1)
        nope = kv.shape[-1] - value
        key = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :],
                                              (n, t, h, rope))], axis=-1)
        query = jnp.concatenate([q_nope.reshape(n, t, h, nope),
                                 q_rope.reshape(n, t, h, rope)], axis=-1)
        if query_scale is not None:
            query = query * _query_factor(
                jnp.arange(t), query_scale)[None, :, None, None]
        v, narrow = kv[..., nope:], nope + rope - value
        if narrow:
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, narrow),))
        ctx = _attn.sdp_attention(
            query.reshape(n, t, -1), key.reshape(n, t, -1),
            v.reshape(n, t, -1), num_heads=h, causal=True, scale=scale)[0]
        if narrow:
            ctx = ctx.reshape(n, t, h, -1)[..., :value].reshape(n, t, -1)
        return ctx


def _infer_latent_write(in_shapes, attrs):
    cache, latent, slot = in_shapes
    return [cache, latent, slot], [cache]


@register("_latent_cache_write", inputs=("cache", "latent", "slot"),
          infer_shape=_infer_latent_write)
def latent_cache_write(cache, latent, slot, **kw):
    """Prefill-side fill of the latent ring: one request's rows ``(1, T,
    width)`` into slot ``slot`` at positions ``[0, T)``, turned to the
    ring's stored order ``(1, width, T)``.  Positions beyond the
    request's true length hold the pad's rows — safe as every ring's are:
    decode masks by length and overwrites position `length` before the
    mask exposes it."""
    slot_i = _attn._as_index(slot).reshape(())
    return lax.dynamic_update_slice(
        cache, latent.swapaxes(1, 2)[:, None].astype(cache.dtype),
        (slot_i, 0, 0, 0))


def _latent_ring_attention(q, new, cache, slot_i, len_i, *, rank, scale):
    """The absorbed step against the latent ring in ``jax.numpy``: what
    runs wherever the TPU kernel does not, and the kernel's oracle.  ``q
    (B, H, width)`` absorbed queries, ``new (B, width)`` → ``(context (B,
    H, rank), cache')``.  Rows are written first, then each row reads its
    whole page — scores against all `width` lines, context from the first
    `rank` — and masks by length."""
    b = q.shape[0]
    ring_len = cache.shape[3]
    ring = _attn._write_rows(cache, new[:, None, :], slot_i, len_i)
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


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block",
                                             "interpret"))
def _latent_decode(q, new, cache, slot_i, len_i, *, rank, scale, block,
                   interpret):
    """The page read on whatever platform the program is lowered for: the
    TPU's kernel (`block` positions a step; `interpret` runs it in
    Pallas's interpreter, for tests) or the ``jax.numpy`` body.  Jitted,
    so that the layers of a decode program trace and lower both once."""
    operands = (q, new, cache, slot_i, len_i)
    body = functools.partial(_latent_ring_attention, rank=rank, scale=scale)
    if block is None:
        return body(*operands)

    def kernel(*operands):
        # lowered once a shape for all programs and processes
        # (ops/exported.py), as the per-head rings' kernel is
        return exported.call(
            "latent_ring_kernel", "latent_ring_attention", operands,
            interpret=interpret, rank=rank, block=block, scale=scale)
    return lax.platform_dependent(*operands, tpu=kernel, default=body)


def _infer_latent_cached(in_shapes, attrs):
    q_nope, q_rope, latent, kvb, cache, slot, length = in_shapes
    ins, outs = _infer_latent([q_nope, q_rope, latent, kvb], attrs)
    return ins + [cache, slot, slot], outs + [cache]


@register("_latent_cached_attention",
          inputs=("q_nope", "q_rope", "latent", "kvb_weight", "cache",
                  "slot", "length"),
          num_outputs=2, infer_shape=_infer_latent_cached)
def latent_cached_attention(q_nope, q_rope, latent, kvb_weight, cache, slot,
                            length, num_heads=1, rope_dim=0, value_dim=0,
                            scale=None, query_scale=None, **kw):
    """One ABSORBED decode step of latent attention against a
    slot-indexed latent ring (slot and length traced operands, as
    ``_cached_attention``'s).  ``q_nope (B, 1, H * nope)``, ``q_rope (B, 1,
    H * rope)`` and ``latent (B, 1, rank + rope)`` of the current token,
    ``kvb_weight (H * (nope + value), rank)``, the ring as the model's
    ``cache_spec`` states it, ``(slots, 1, rank + rope, ring_len)``.

    The step's row is written at ``cache[slot, 0, :, length]`` FIRST, then
    each row attends over its own page's positions ``0..length``.  Padded
    rows of a partial decode batch point at the scratch slot with length
    0, as every ring's do.  No per-head K or V exists at any point.

    Outputs: context ``(B, 1, H * value)`` and the updated ring."""
    h, rope, value = (int(_lit(v)) for v in (num_heads, rope_dim, value_dim))
    b = q_nope.shape[0]
    rank = latent.shape[-1] - rope
    len_i = _attn._as_index(length)
    kvb = kvb_weight.reshape(h, -1, rank)
    nope = kvb.shape[1] - value
    with jax.named_scope("mx:mla.absorb"):
        q = jnp.concatenate(
            [jnp.einsum("bhd,hdr->bhr", q_nope.reshape(b, h, nope),
                        kvb[:, :nope]), q_rope.reshape(b, h, rope)], axis=-1)
        if query_scale is not None:
            q = q * _query_factor(len_i, query_scale)[:, None, None]
    with jax.named_scope("mx:mla.ring"):
        u, ring = _latent_decode(
            q, latent.reshape(b, -1), cache, _attn._as_index(slot), len_i,
            rank=rank, interpret=_attn._INTERPRET,
            scale=((nope + rope) ** -0.5 if scale is None
                   else float(_lit(scale))),
            # the block a lowering for the TPU would use; which platform
            # the program is lowered for is not known here
            block=_attn.decode_block(cache.shape, "tpu",
                                     cache.dtype.itemsize, latent=True))
    with jax.named_scope("mx:mla.absorb"):
        ctx = jnp.einsum("bhr,hvr->bhv", u, kvb[:, nope:])
    return ctx.reshape(b, 1, h * value), ring
