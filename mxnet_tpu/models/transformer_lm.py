"""Transformer language model — the first transformer in the zoo
(ROADMAP item 2: "modern traffic" for the serving tier, and the first
real SP-runtime consumer outside dryrun).

One :class:`TransformerLM` spec builds FIVE graphs over ONE parameter
set (shared names, so a training checkpoint serves directly):

* :meth:`sym_gen` — the BucketingModule factory: full-sequence
  causal-LM training graph (embedding + learned positions, pre-LN
  blocks with fused ``_sdp_attention``, weight-tied softmax head with
  the pad label ignored).  Attention is ONE graph node per layer, so
  every sequence bucket traces the same node count and buckets differ
  only by shape — exactly what the bucketed compile-once machinery
  wants.
* :meth:`score_symbol` — the same forward emitting raw per-position
  logits ``(N, T, vocab)``: the decode-parity reference
  (tests/test_transformer_lm.py; the generate cells' ``logit_rel_err``).
* :meth:`prefill_symbol` — serving prefill: run the prompt through a
  sequence bucket, write each layer's per-head K/V block into the
  session's KV-ring slot (``_kv_cache_write``), and emit the
  next-token logits from the prompt's true tail (``_take_step``), all
  in one dispatch.
* :meth:`decode_symbol` — one token-level decode step for a PACKED
  batch of sessions: slot + length ride as traced operands into
  ``_cached_attention``, so one compiled program per decode bucket
  serves any join/leave mix (serving/decode.py).
* :meth:`mixed_symbol` — the two in ONE program (PR 46): one prompt's
  prefill AND one decode step of the session's packed rows, the prompt's
  ``T`` positions and the rows' tokens concatenated along the token axis
  wherever a layer is a dense product (embedding, the mixers'
  projections, the FFN and its router, final norm and head), so every
  weight is read once; only the mixers' cores split, each kind's `mixed`
  calling its prefill's and its decode's core around shared
  projections.  A model with a kind that has none (Mamba-2, whose
  bucket programs are compute-bound and the costliest to build; latent
  attention) returns None and keeps the two programs.

The serving graphs thread the KV rings functionally (caches in ->
updated caches out); on TPU the serve program's donated-input tuple
turns that into an in-place update.  One more vector rides with the
rings, ``last_token (slots + 1,)``: both serving graphs sample the
greedy token of their logits on the device (``_greedy_token``), return
it as a small output and write it at ``last_token[slot]``; a decode row
whose ``data`` is negative takes its token from there (``_token_feed``),
so the batcher can dispatch a step before it has read the one before.

The block's choices are arguments of the ONE spec, not a second model
file: with the defaults the block is OPT's (learned positions,
LayerNorm, ReLU FFN, biases, tied head); ``norm="rms"``,
``positions="rotary"``, ``qk_norm=True``, ``num_experts=E`` with
``experts_per_token=k`` (a dropless routed SwiGLU layer of width
`d_ff`, ``mx.sym.MoE``), ``bias=False`` and ``tied_head=False``
together are OLMoE's.  The helper methods branch; the five graph
builders are shared.  A routed model's serving graphs end with one
more small output, ``moe_load (num_layers, num_experts)`` — tokens per
expert in this call — which the batcher books as the ``moe.*`` counters.

**Layer kinds.**  `layer_types` names each layer's MIXER — the half of
the block before the FFN — ``"attention"`` (the default for every layer),
``"window_attention"`` (attention over a sliding window of
`sliding_window` positions), ``"mamba"`` (a Mamba-2 state-space mixer,
ops/ssm.py), ``"linear_attention"`` (a Gated DeltaNet delta-rule mixer,
ops/gdn.py: `heads` value heads, one state each, over `key_heads` heads of
q and k — as many unless the spec says fewer, each then read by a group of
value heads) or ``"latent_attention"``
(multi-head latent attention, ops/latent.py: a low-rank query, ONE cached
row a position for all heads — the normed joint down-projection and a
rotary key of its own projection — which the full-sequence forms
up-project per head and the decode step absorbs into the query).  A kind
is one class below
(:class:`_Attention`, :class:`_WindowAttention`, :class:`_Mamba2`,
:class:`_GatedDeltaNet`, :class:`_LatentAttention`) that
declares, in that one place, its sizes, its parameters, its full-sequence
forward, its prefill, its decode step, the two in one (`mixed`: attention,
window attention and Gated DeltaNet have one), the device-resident state it
keeps between calls and the counters a program call adds to; the five
graph builders walk the pattern and know no kind by name.  **A layer is
a mixer, an FFN or both, never neither:** ``"none"`` names the half a layer
does not have (:class:`_Nothing`, in `layer_types` and in `ffn_types`
alike) — no parameters, no norm, no cache entry, no counters, no join —
so a stream of sublayers, each ``h <- h + F(norm(h))`` with ONE norm, is
one layer a sublayer.  `ffn_types`
names each layer's FFN — the other half — the same way: ``"dense"``
(:class:`_DenseFFN`, of width `d_ff`), ``"routed"``
(:class:`_RoutedFFN`: ``mx.sym.MoE`` experts of width `expert_d_ff`, a
shared expert, a held range, zero-compute experts) or ``"shortcut"``
(:class:`_ShortcutFFN`: a dense FFN now and, forked from the same normed
input, a routed layer whose result joins the stream at the END OF THE NEXT
layer); by default every layer's is the one `num_experts` implies.
``num_kv_heads`` (grouped-query attention), ``positions="none"``,
``ffn="swiglu"`` (a dense gated FFN), ``attention_multiplier`` and the
three stream multipliers (``embedding_`` / ``residual_multiplier``,
``logits_scaling``) with ``layer_types`` of nine ``"mamba"`` to one
``"attention"`` are Granite 4.0-H's.  ``block_norm="output"`` (each
branch's OUTPUT is normed before it joins the stream, ``h + norm(f(h))``,
where every block before normed its input), ``qk_norm``,
``positions="none"``, ``ffn="swiglu"``, an untied head and ``layer_types``
of three ``"linear_attention"`` to one ``"attention"`` are Olmo-Hybrid's.
``head_dim`` (a head width that is not ``d_model / num_heads``),
``qk_norm="head"``, ``out_gate``, ``block_norm="both"``, positions by
attention kind (``{"window_attention": "rotary"}``: the full layers have
no position signal), ``layer_types`` of three ``"window_attention"`` to
one ``"attention"``, ``ffn_types`` of leading ``"dense"`` layers before
``"routed"`` ones, ``router_score="sigmoid"`` with ``router_bias``,
``route_norm``, ``route_scale``, ``shared_d_ff`` and ``held_experts`` are
Trinity's (`afmoe`), held as one chip's share of its experts.
``layer_types`` of three ``"linear_attention"`` to one ``"attention"`` with
``ffn_types`` all ``"routed"`` (a recurrent mixer and an expert FFN in ONE
block), the delta rule's `key_heads` under twice as many `heads` with
``neg_eigval=False``, ``head_dim=256`` with ``qk_norm="head"``,
``out_gate`` and ``rotary_dim`` (the first quarter of each head turned),
softmax ``route_norm`` experts of `expert_d_ff` with a ``shared_gate`` on
the shared expert and ``held_experts`` are Qwen3-Next's (`qwen3_next`);
its norm gains are stored as they are applied, ``1 + w`` of the published
``w``.
``layer_types`` all ``"latent_attention"`` with the kind's `q_rank`,
`kv_rank`, `nope_dim` + `rope_dim` >= `value_dim`, ``rope_scaling`` (YaRN's
blended frequencies on the rotary part alone),
``attention_multiplier`` (the softmax scale with YaRN's ``mscale`` square),
``query_scale`` (the query grows with the logarithm of its position past
the trained length), and softmax ``route_norm`` experts of `expert_d_ff`
beside an ungated shared expert with ``held_experts`` are
Mistral-Small-4's (`mistral4`).
``layer_types`` of ``"sparse_latent_attention"`` and three
``"window_latent_attention"`` a period with `kind_specs` giving EACH KIND ITS
OWN heads, ranks, widths (a key of ``nope_dim + rope_dim`` beside a value of
`value_dim`), rotary base, `head_gate` and `lora_rescale` — and the full
kind its indexer's `index_heads` x `index_dim` and `index_topk`, the window
kind its `window` —, ``ffn_types`` of a leading ``"dense"`` layer before
``"routed"`` ones, sigmoid scores with ``router_bias`` and ``held_experts``
are dots3-note-prev's (`dots3`).
``layer_types`` of one ``"attention"`` WITHOUT a position signal to three
``"window_attention"`` with rotary positions, 28 query heads over 4 K/V
heads (groups of seven), every layer ``"routed"`` with ``router_input=
"mixer"`` (the router scores what the block's mixer reads, before the
mixer runs), ``expert_act="relu"`` (a ReLU gate), ``route_norm`` and no
shared expert, an untied head: SmallThinker's (`smallthinker`).
``layer_types`` all ``"latent_attention"`` with a value of 128 under a key
of 128 + 64 and ``latent_lora_rescale``, ``ffn_types`` of ``("shortcut",
"dense")`` a PUBLISHED layer — two latent-attention sublayers, two dense
SwiGLUs of `d_ff` and one routed layer across them —, ``zero_experts`` 256
behind 512 real ones in a softmax router used unnormalised times
``route_scale`` with ``router_bias``, held as one chip's share of experts
AND of heads (`num_heads` is then the share's count): LongCat-Flash's
(`longcat_flash`).
``layer_types`` / ``ffn_types`` read off a pattern of SUBLAYERS, one layer
a character — ``("mamba", "none")``, ``("attention", "none")``, ``("none",
"routed")`` —, the Mamba-2 kind's `groups` 8 (of `B` / `C` and of the gated
norm), NoPE attention of 32 heads over 2, ``expert_gated=False``
with ``expert_act="relu2"`` (experts of TWO matrices, ``W2 relu(W1 x)^2``,
the shared one too), sigmoid scores with ``router_bias``, ``route_norm``
and ``route_scale``, ``held_experts``, an untied head: Nemotron-H's
(`nemotron_h`).

**A draft module.**  `nextn` = 1 adds DeepSeek-V3's multi-token-prediction
module behind the trunk (GLM-5's, `glm5`): two norms (``mtp_enorm``,
``mtp_hnorm``), ``mtp_eh_weight (d_model, 2 d_model)``, ONE more block of
the last layer's own kinds as layer ``num_layers`` (parameters
``l<num_layers>_*``, cache entries of its own in :meth:`cache_spec`), a head
norm ``mtp_ln_f``; the embedding and the head are the trunk's.  At
position p it takes the trunk's last stream BEFORE ``ln_f`` and the token
at p + 1 and predicts the token at p + 2.  The two serving graphs use it
as the model's own DRAFT: a prefill runs it over the prompt (tokens
shifted by one, the first sampled token at the tail) and leaves a draft
beside the first token; a decode step runs TWO rows a session — the last
verified token at position n and the draft at n + 1 —, keeps the draft
where it is the trunk's own argmax, emits one or two tokens and drafts
again (ops/attention.py ``_draft_*``).  ``last_token`` is then ``(3,
slots + 1)`` — token, draft, position: :meth:`token_state` — the
``token`` output ``(B, 3)`` = ``[count, first, second]``, and the
``logits`` output stacks the trunk's rows over the module's
(:meth:`decode_symbol`).  No mixed step; the training graph leaves the
module out (there it is one more loss term).

**Per-kind sizes.**  A mixer kind that has sizes of its own OWNS them:
its class declares them (`SIZES`, `OPTIONS` with their defaults), reads
``kind_specs[kind]`` ``{size: value}`` and validates where it is built
(`_own_sizes`) — the constructor knows none of them.  The three kinds older
than `kind_specs` are also spelt flat, ``<prefix><size>`` (``mamba_heads``,
``linear_key_dim``, ``latent_q_rank``: `_FLAT`), which IS
``kind_specs[kind][size]``.  The model-wide arguments above stay the
model's; the latent kinds that differ in heads or rotary base say so in
their own mapping.

**Cache spec.**  :meth:`TransformerLM.cache_spec` is the ONE statement of
what a serving session holds on the device between calls: an ordered
``{name: CacheEntry(kind, shape)}`` over all layers — an attention layer's
two KV rings (kind ``"ring"``, pages addressed by slot and masked by
length, so stale contents are harmless; A RING'S LENGTH IS ITS KIND'S,
the last axis of its shape: the session's ``max_len`` for a full layer,
``min(sliding_window, max_len)`` for a window layer, whose ring a longer
session writes modulo), a latent-attention layer's ONE latent ring (kind
``"latent"``, ``(slots, 1, kv_rank + rope_dim, max_len)``:
addressed, masked and counted like the rings, with no second ring beside
it and no per-head K or V anywhere; a window latent layer's is
``min(window, max_len)`` long and written modulo), a sparse latent layer's
INDEX KEYS beside its latent ring (kind ``"index"``, ``(slots, 1, index_dim,
max_len)``: one key a position that the layer's indexer scores; whoever
sizes, charges or zeroes session state takes it like any entry, and only
the rings' position counters leave it out) and a Mamba or Gated DeltaNet layer's
conv window and recurrent state (kind ``"state"``, a fixed size a slot
whatever the context, wholly rewritten by a prefill).  The serving graphs take and
return exactly these names in this order; whoever allocates, sizes,
charges or counts session state asks here.
"""
from __future__ import annotations

import ast
import math
from typing import NamedTuple

from .. import symbol as sym
from ..attribute import AttrScope
from ..ops import (attention as _attention, gdn as _gdn,
                   sparse_latent as _sparse_latent, ssm as _ssm)

__all__ = ["TransformerLM", "CacheEntry"]


class CacheEntry(NamedTuple):
    """One device buffer a serving session threads through its calls:
    `kind` ``"ring"`` (a K or a V ring's pages, masked by length;
    ``shape[3]`` is the ring's own length in positions, which differs by
    layer kind), ``"latent"`` (a latent-attention layer's ONE ring, ``(slots,
    1, width, positions)``: a ring in every respect, of one row a
    position that all heads read as key and, its leading lines, as value),
    ``"index"`` (a sparse latent layer's index keys, ``(slots, 1, width,
    positions)``: read by that layer's indexer alone, no ring to anyone
    who counts rings) or ``"state"`` (a recurrent layer's, overwritten
    whole by a prefill);
    `shape` as stored, float32."""

    kind: str
    shape: tuple

    @property
    def nbytes(self):
        return 4 * math.prod(self.shape)


class _Rows(NamedTuple):
    """The packed decode rows that ride a mixed step: how many the program
    has (`n`, static), and their `slot` and `length` operands ``(n,)``."""

    n: int
    slot: object
    length: object


def _split_rows(x, rows, name):
    """A mixed step's tokens ``(1, T + rows, W)`` as the prompt's
    positions ``(1, T, W)`` and the packed rows ``(rows, 1, W)``."""
    prompt = sym.slice_axis(x, axis=1, begin=0, end=-rows,
                            name=name + "_prompt")
    tail = sym.slice_axis(x, axis=1, begin=-rows, end=None,
                          name=name + "_tail")
    return prompt, sym.SwapAxis(tail, dim1=0, dim2=1, name=name + "_rows")


def _join_rows(prompt, rows, name):
    """The inverse: ``(1, T, W)`` and ``(rows, 1, W)`` as one ``(1, T +
    rows, W)``, the prompt's positions first."""
    return sym.Concat(prompt, sym.SwapAxis(rows, dim1=0, dim2=1,
                                           name=name + "_tail"),
                      dim=1, name=name + "_tokens")


def _prefill_counters(positions, width, heads, kv_heads, platform):
    """What a prefill (or a mixed step) of a bucket of `positions` adds
    for ONE layer whose prompt goes through ``_sdp_attention`` with a
    query `width` wide: the bucket's positions (the pad included), and
    those of them that a program lowered for `platform` runs through the
    TPU's blockwise kernel (all, or none where
    ``ops.attention.prefill_block`` says the ``jax.numpy`` body runs)."""
    tiled = _attention.prefill_block((1, positions, width), heads, kv_heads,
                                     platform) is not None
    return {"attn.prefill_positions": positions,
            "attn.kernel_positions": positions * tiled}


# how callers from before `kind_specs` spell three kinds' sizes: a keyword
# ``<prefix><size>`` of the constructor IS ``kind_specs[kind][size]``
_FLAT = {"mamba": "mamba_", "linear_attention": "linear_",
         "latent_attention": "latent_"}


def _own_sizes(mixer, lm):
    """What a kind with sizes reads of the spec, ``lm.kind_specs[KIND]``
    and nothing else of them, as ``{name: value}``: each of the kind's
    `SIZES` an int >= 1, its `OPTIONS` ``{name: default}`` as the mapping
    sets them (a value of its default's type), None "not set", no
    other key, and the kind's own `_fits` of them — `NEEDS` in words —
    true.  ValueError otherwise, naming the kind and its sizes as its
    callers spell them."""
    spec = dict(lm.kind_specs.get(mixer.KIND) or {})
    given = {k: v for k, v in spec.items() if v is not None}
    own = {k: int(given.get(k, 0)) for k in mixer.SIZES}
    for k, default in mixer.OPTIONS.items():
        value = given.get(k, default)
        own[k] = type(default)(value)
    if (set(spec) - set(own) or min(own[k] for k in mixer.SIZES) < 1
            or not mixer._fits(own)):
        flat = _FLAT.get(mixer.KIND, "")
        raise ValueError(
            "a %r layer needs %s >= 1, %s; it may set %s; kind_specs[%r] is "
            "%r" % (mixer.KIND, ", ".join(flat + k for k in mixer.SIZES),
                    mixer.NEEDS,
                    ", ".join(flat + k for k in sorted(mixer.OPTIONS))
                    or "nothing else",
                    mixer.KIND, spec))
    return own


class _Attention:
    """The attention mixer of layer i: fused QKV projection (and the
    output gate's, where the spec has one), QK-norm and rotary as the
    spec says, causal softmax attention over `num_heads` query heads and
    `num_kv_heads` K/V heads of `d_head`, output projection.  State: two
    KV rings of the session's `max_len` positions."""

    KIND = "attention"

    def __init__(self, lm):
        self.lm = lm
        self.rope = lm.kind_positions[self.KIND] == "rotary"
        self.q_width = lm.num_heads * lm.d_head
        self.kv_width = lm.num_kv_heads * lm.d_head
        # what the three attention ops take beyond their operands; an
        # option appears on a node only when the spec sets it
        self.attrs = dict(num_heads=lm.num_heads)
        self.sdp_attrs = dict(num_heads=lm.num_heads, causal=True)
        for attrs in (self.attrs, self.sdp_attrs):
            if lm.num_kv_heads != lm.num_heads:
                attrs["num_kv_heads"] = lm.num_kv_heads
            if lm.attention_multiplier is not None:
                attrs["scale"] = lm.attention_multiplier

    def params(self, i):
        lm, v = self.lm, sym.Variable
        d = lm.d_model
        wide = (1 + lm.out_gate) * self.q_width + 2 * self.kv_width
        p = {"qkv_weight": v("l%d_qkv_weight" % i, shape=(wide, d)),
             "out_weight": v("l%d_out_weight" % i, shape=(d, self.q_width))}
        if lm.bias:
            p["qkv_bias"] = v("l%d_qkv_bias" % i, shape=(wide,))
            p["out_bias"] = v("l%d_out_bias" % i, shape=(d,))
        return p

    def ring_len(self, max_len):
        """Positions of this kind's rings in a session of `max_len`."""
        return int(max_len)

    def cache_spec(self, i, slots, max_len):
        """``(slots, num_kv_heads, d_head, ring_len)`` for K and for V:
        the POSITIONS on the minor axis, for every head width.

        It is the order the decode step's attention reads (ops/
        attention.py): scores reduce over d_head and the context over
        positions without moving either, and the TPU kernel's operands
        have this layout as they are stored — no copy, no padding of a
        64-wide head to 128 lanes.  A step's new row is one position of
        every ``(head, d_head)`` line, so it is written as part of the
        128-position block that holds it (PERF.md section 6, PR 32;
        PR 26 had found the runtime keeping 64-wide heads in this very
        order under the old shape)."""
        ring = CacheEntry("ring", (int(slots), self.lm.num_kv_heads,
                                   self.lm.d_head, self.ring_len(max_len)))
        return [("k_cache_%d" % i, ring), ("v_cache_%d" % i, ring)]

    def _head_norm(self, x, name, heads):
        """QK-norm of one projection: over all its channels, or — the
        spec's ``qk_norm="head"`` — each head on its own with one
        ``(d_head,)`` gain for all of them."""
        lm = self.lm
        if lm.qk_norm != "head":
            return lm._norm(x, name, width=heads * lm.d_head)
        gamma = sym.Variable(name + "_gamma", shape=(lm.d_head,))
        return sym.RMSNorm(x, gamma=gamma, eps=lm.norm_eps, num_heads=heads,
                           name=name)

    def _project(self, x, p, i):
        """The fused projection of the normed stream, split and QK-normed
        — what every token takes alike, whatever its position: ``[q, k,
        v, gate]``, `gate` the output gate's projection or None."""
        lm = self.lm
        q_width, kv_width = self.q_width, self.kv_width
        widths = [q_width, kv_width, kv_width] + [q_width] * lm.out_gate
        qkv = lm._linear(x, p, "qkv", sum(widths), "l%d_qkv" % i)
        if widths == [lm.d_model] * 3:
            parts = list(sym.SliceChannel(qkv, num_outputs=3, axis=2,
                                          name="l%d_qkv_split" % i))
        else:
            edges = [sum(widths[:n]) for n in range(len(widths) + 1)]
            parts = [sym.slice_axis(qkv, axis=2, begin=a, end=b,
                                    name="l%d_%s_split" % (i, n))
                     for n, a, b in zip("qkvg", edges, edges[1:])]
        if lm.qk_norm:
            parts[0] = self._head_norm(parts[0], "l%d_qnorm" % i,
                                       lm.num_heads)
            parts[1] = self._head_norm(parts[1], "l%d_knorm" % i,
                                       lm.num_kv_heads)
        return parts + [None] * (not lm.out_gate)

    def _turn(self, q, k, i, index=None, tag=""):
        """Rotary positions on Q and K where this kind has them — each
        row's own `index` in a decode step, 0..T-1 without one — so K
        reaches the ring already rotated."""
        lm = self.lm
        if not self.rope:
            return q, k
        rope = dict(theta=lm.rope_theta)
        if lm.rotary_dim is not None:
            rope["rotary_dim"] = lm.rotary_dim
        turned = []
        for t, n, h in zip((q, k), "qk", (lm.num_heads, lm.num_kv_heads)):
            name = "l%d_%s%srope" % (i, tag, n)
            turned.append(
                sym._rotary(t, name=name, num_heads=h, **rope)
                if index is None else
                sym._rotary_at(t, index, name=name, num_heads=h, **rope))
        return turned

    def _qkv(self, x, p, i, index=None):
        """The projections of the normed stream, ready for the attention
        op: ``(q, k, v, gate)``."""
        q, k, v, gate = self._project(x, p, i)
        q, k = self._turn(q, k, i, index)
        return q, k, v, gate

    def _out(self, ctx, gate, p, i):
        if gate is not None:
            ctx = ctx * sym.Activation(gate, act_type="sigmoid",
                                       name="l%d_out_gate" % i)
        return self.lm._linear(ctx, p, "out", self.lm.d_model,
                               "l%d_proj" % i)

    def _attend(self, q, k, v, i):
        return sym._sdp_attention(q, k, v, name="l%d_attn" % i,
                                  **self.sdp_attrs)

    def counters(self, i, positions=0, platform=None, **call):
        """What one program call adds: the bucket positions a prefill (or
        a mixed step) attends among themselves in this layer, and those
        of them the TPU's blockwise kernel takes (`_prefill_counters`)."""
        return _prefill_counters(positions, self.q_width, self.lm.num_heads,
                                 self.lm.num_kv_heads, platform)

    def full(self, x, p, i):
        q, k, v, gate = self._qkv(x, p, i)
        return self._out(self._attend(q, k, v, i)[0], gate, p, i)

    def _ring_write(self, caches, i, attn, slot, length):
        return sym._kv_cache_write(
            caches["k_cache_%d" % i], caches["v_cache_%d" % i],
            attn[1], attn[2], slot, name="l%d_kv_write" % i)

    def _fill(self, q, k, v, i, caches, slot, length):
        """A prompt's positions the prefill way: causal attention among
        themselves, their K/V written into the rings' page `slot`.
        Returns (context, the two rings)."""
        attn = self._attend(q, k, v, i)
        wrote = self._ring_write(caches, i, attn, slot, length)
        return attn[0], [wrote[0], wrote[1]]

    def _step(self, q, k, v, i, rings, slot, length, tag=""):
        """Packed rows the decode way: one token each against its own
        page of `rings` (K's, V's).  Returns (context, the two rings)."""
        step = sym._cached_attention(
            q, k, v, *rings, slot, length, name="l%d_%sattn" % (i, tag),
            **self.attrs)
        return step[0], [step[1], step[2]]

    def prefill(self, x, p, i, caches, slot, length):
        q, k, v, gate = self._qkv(x, p, i)
        ctx, rings = self._fill(q, k, v, i, caches, slot, length)
        return self._out(ctx, gate, p, i), rings

    def decode(self, x, p, i, caches, slot, length):
        q, k, v, gate = self._qkv(x, p, i, index=length)
        rings = [caches["k_cache_%d" % i], caches["v_cache_%d" % i]]
        ctx, rings = self._step(q, k, v, i, rings, slot, length)
        return self._out(ctx, gate, p, i), rings

    def mixed(self, x, p, i, caches, slot, length, rows):
        """The prefill of one prompt AND the decode step of `rows` packed
        rows around ONE projection each way: `x` ``(1, T + rows.n, d)``
        holds the prompt's positions, then the rows' tokens.  Only the
        core splits — the prompt's positions fill page `slot`, then the
        rows step against the rings so written."""
        q, k, v, gate = self._project(x, p, i)
        (q, q_r), (k, k_r), (v, v_r) = (
            _split_rows(t, rows.n, "l%d_%s" % (i, n))
            for t, n in zip((q, k, v), "qkv"))
        q, k = self._turn(q, k, i)
        q_r, k_r = self._turn(q_r, k_r, i, index=rows.length, tag="row_")
        ctx, rings = self._fill(q, k, v, i, caches, slot, length)
        ctx_r, rings = self._step(q_r, k_r, v_r, i, rings, rows.slot,
                                  rows.length, tag="row_")
        return self._out(_join_rows(ctx, ctx_r, "l%d_ctx" % i), gate, p,
                         i), rings


class _WindowAttention(_Attention):
    """The attention mixer with a sliding window: row i attends to ``j <=
    i`` with ``i - j < sliding_window`` (itself and the W - 1 before it).
    State: two KV rings of ``min(W, max_len)`` positions, whatever the
    session's length — a prefill writes the prompt's last W positions and
    a decode step writes at ``length mod W`` (ops/attention.py), so a
    ring that is full holds exactly the window."""

    KIND = "window_attention"

    def __init__(self, lm):
        super().__init__(lm)
        self.attrs["window"] = self.sdp_attrs["window"] = lm.sliding_window

    def ring_len(self, max_len):
        return min(self.lm.sliding_window, int(max_len))

    def _ring_write(self, caches, i, attn, slot, length):
        return sym._kv_cache_write(
            caches["k_cache_%d" % i], caches["v_cache_%d" % i],
            attn[1], attn[2], slot, length, window=self.lm.sliding_window,
            name="l%d_kv_write" % i)

    def counters(self, i, positions=0, platform=None, rows=0, lengths=(),
                 pages=0, max_len=None, **call):
        """What every attention layer adds; of a prefill, the key blocks
        the TPU's blockwise kernel visits of one K/V head under the window
        and those a causal prefill of the bucket would
        (``ops.attention.prefill_visits``; one each where the ``jax.numpy``
        body computes every score); and of one decode step: a window row
        for each real row of this layer, those of them whose ring has
        wrapped (``length >= W``: the row is written modulo and the whole
        ring is read), and the bytes of this layer's rings among the
        `pages` pages bound."""
        lm = self.lm
        page = sum(e.nbytes for _, e in self.cache_spec(
            i, 1, lm.max_len if max_len is None else max_len))
        wrapped = sum(1 for n in lengths if n >= lm.sliding_window)
        band = causal = 0
        if positions:
            # the ``jax.numpy`` body computes ONE block, the whole square
            block = _attention.prefill_block(
                (1, positions, self.q_width), lm.num_heads, lm.num_kv_heads,
                platform) or (positions, positions)
            band, causal = (_attention.prefill_visits(positions, block, w)
                            for w in (lm.sliding_window, None))
        return dict(
            super().counters(i, positions=positions, platform=platform),
            **{"attn.band_blocks": band, "attn.causal_blocks": causal,
               "kv.window_rows": rows, "kv.wrapped_rows": wrapped,
               "cache.window_bytes": pages * page})


class _KindLatent:
    """What the three latent kinds share (ops/latent.py and
    ops/sparse_latent.py have the equations), all of the KIND'S OWN,
    ``kind_specs[KIND]``: `num_heads` heads; a low-rank query ``c_q =
    norm(x W_qa)`` of rank `q_rank`, ``q = c_q W_qb`` of heads of
    ``[q_nope | q_rope]``; a joint down-projection ``[c_kv | k_r] = x
    W_kva`` with a norm on ``c_kv`` alone and ONE rotary key ``k_r`` for
    all heads — ONE cached row of ``kv_rank + rope_dim`` a position; the
    per-head up-projection `W_kvb` ``(H * (nope_dim + value_dim),
    kv_rank)``: a head's key ``nope_dim + rope_dim`` wide beside a value
    of `value_dim` (the two need not agree), rotary base `rope_theta`;
    `lora_rescale` multiplies the normed latents by ``sqrt(d_model /
    rank)`` of their own rank; `head_gate` multiplies each head's context
    by ``sigmoid(x w_h)``, ONE scalar a head, ``l<i>_hgate_weight
    (num_heads, d_model)``, before the output projection; the softmax
    scale is ``(nope_dim + rope_dim)^-1/2``.  No bias anywhere; `W_qb`'s
    rows lie BY KIND, all heads' ``q_nope`` then all heads' ``q_rope``,
    and a rotary part's channels in the rotate-half order (a checkpoint
    that interleaves the pairs is permuted once, on loading).  State: ONE
    latent ring a layer, ``(slots, 1, kv_rank + rope_dim, max_len)`` — a
    row a position for all heads, no per-head K or V anywhere.  The two
    kinds under a mask hand a whole sequence's query LATENT to one op node
    that up-projects a group of heads at a time (`_masked_operands`);
    every decode step projects the queries and absorbs."""

    SIZES = ("num_heads", "q_rank", "kv_rank", "nope_dim", "rope_dim",
             "value_dim")
    OPTIONS = {"rope_theta": 10000.0, "lora_rescale": False,
               "head_gate": False}
    NEEDS = "rope_dim even"

    def __init__(self, lm):
        self.lm = lm
        self.sizes = own = _own_sizes(self, lm)
        self.heads = own.get("num_heads") or lm.num_heads
        (self.q_rank, self.rank, self.nope, self.rope,
         self.value) = (own[k] for k in _KindLatent.SIZES[1:])
        for k, default in _KindLatent.OPTIONS.items():
            setattr(self, k, own.get(k, default))
        self.width = self.rank + self.rope
        # what every attention node of the kind takes
        self.attrs = dict(num_heads=self.heads, rope_dim=self.rope,
                          value_dim=self.value)
        self.rope_attrs = {}   # and every rotary node, beyond the base

    def _fits(self, own):
        return own["rope_dim"] % 2 == 0

    def params(self, i):
        lm, v = self.lm, sym.Variable
        d, h = lm.d_model, self.heads
        p = {
            "qa_weight": v("l%d_qa_weight" % i, shape=(self.q_rank, d)),
            "qb_weight": v("l%d_qb_weight" % i,
                           shape=(h * (self.nope + self.rope), self.q_rank)),
            "kva_weight": v("l%d_kva_weight" % i, shape=(self.width, d)),
            "kvb_weight": v("l%d_kvb_weight" % i,
                            shape=(h * (self.nope + self.value), self.rank)),
            "out_weight": v("l%d_out_weight" % i,
                            shape=(d, h * self.value))}
        if self.head_gate:
            p["hgate_weight"] = v("l%d_hgate_weight" % i, shape=(h, d))
        return p

    def cache_spec(self, i, slots, max_len):
        """ONE entry: ``(slots, 1, kv_rank + rope_dim, max_len)``, kind
        ``"latent"`` — the positions on the minor axis like every ring,
        one head of `width` lines that every query head reads."""
        return [("latent_cache_%d" % i, CacheEntry(
            "latent", (int(slots), 1, self.width, int(max_len))))]

    def _fc(self, x, p, key, width, name):
        return sym.FullyConnected(x, weight=p[key + "_weight"],
                                  num_hidden=width, no_bias=True,
                                  flatten=False, name=name)

    def _turn(self, t, name, heads, index, **rope):
        attrs = dict(rope, theta=float(self.rope_theta), **self.rope_attrs,
                     num_heads=heads, name=name)
        return (sym._rotary(t, **attrs) if index is None
                else sym._rotary_at(t, index, **attrs))

    def _rescaled(self, c, rank):
        if not self.lora_rescale:
            return c
        return c * (self.lm.d_model / rank) ** 0.5

    def _latents(self, x, p, i, index=None):
        """``(c_q, latent)`` of the normed stream: the query latent
        (normed, rescaled) and ``latent = [c | k_r]``, the row the ring
        keeps — ``c`` normed and rescaled, the ONE rotary key turned (each
        row's own `index` in a decode step, 0..T-1 without one)."""
        lm = self.lm
        c_q = self._rescaled(
            lm._norm(self._fc(x, p, "qa", self.q_rank, "l%d_qa" % i),
                     "l%d_qa_norm" % i, width=self.q_rank), self.q_rank)
        kva = self._fc(x, p, "kva", self.width, "l%d_kva" % i)
        c = self._rescaled(
            lm._norm(sym.slice_axis(kva, axis=2, begin=0, end=self.rank,
                                    name="l%d_c_kv" % i),
                     "l%d_kva_norm" % i, width=self.rank), self.rank)
        k_r = self._turn(sym.slice_axis(kva, axis=2, begin=self.rank,
                                        end=self.width, name="l%d_k_r" % i),
                         "l%d_krope" % i, 1, index)
        return c_q, sym.Concat(c, k_r, dim=2, name="l%d_latent" % i)

    def _queries(self, c_q, p, i, index, end=None):
        """``(q_nope, q_rope)`` of the query latent (`end`: how a kind's
        graphs spell where ``q_rope`` ends)."""
        h = self.heads
        q = self._fc(c_q, p, "qb", h * (self.nope + self.rope), "l%d_qb" % i)
        q_nope = sym.slice_axis(q, axis=2, begin=0, end=h * self.nope,
                                name="l%d_q_nope" % i)
        q_rope = sym.slice_axis(q, axis=2, begin=h * self.nope, end=end,
                                name="l%d_q_rope" % i)
        return q_nope, self._turn(q_rope, "l%d_qrope" % i, h, index)

    def _gate(self, x, p, i):
        return sym.Activation(
            self._fc(x, p, "hgate", self.heads, "l%d_hgate" % i),
            act_type="sigmoid", name="l%d_head_gate" % i)

    def _masked_operands(self, x, p, i):
        """(operands of the whole-sequence node, its attributes, the
        entries' rows a prefill writes)."""
        c_q, latent = self._latents(x, p, i)
        operands = [c_q, p["qb_weight"], latent, p["kvb_weight"]]
        attrs = dict(self.attrs, nope_dim=self.nope,
                     theta=float(self.rope_theta))
        if self.head_gate:
            operands.append(self._gate(x, p, i))
            attrs["gated"] = True
        return operands, attrs, [latent]

    def _out(self, ctx, p, i):
        return self._fc(ctx, p, "out", self.lm.d_model, "l%d_proj" % i)

    def _gated_out(self, ctx, x, p, i):
        """A decode step's context ``(B, 1, H * value)``, each head's times
        its gate, projected."""
        if self.head_gate:
            gate = sym.Reshape(self._gate(x, p, i), shape=(0, 0, -1, 1),
                               name="l%d_gate_heads" % i)
            ctx = sym.Reshape(
                sym.broadcast_mul(
                    sym.Reshape(ctx, shape=(0, 0, self.heads, self.value),
                                name="l%d_ctx_heads" % i), gate,
                    name="l%d_ctx_gated" % i),
                shape=(0, 0, -1), name="l%d_ctx_flat" % i)
        return self._out(ctx, p, i)

    def full(self, x, p, i):
        return self._expanded(x, p, i)[0]

    def _latent_counters(self, i, positions, computed, pages, max_len, read,
                         tiled=False):
        """What every latent kind adds: a bucket's positions (and those of
        them a blockwise kernel of the TPU takes: all where the kind has
        one and the program is `tiled`, else none), a latent layer-step
        (none by the latent ring's kernel: the plain kind's own), the
        bytes of the `read`
        positions of this layer's pages a step reads, this layer's ring
        among the `pages` pages bound."""
        entry = dict(self.cache_spec(
            i, 1, self.lm.max_len if max_len is None else max_len))[
                "latent_cache_%d" % i]
        return {"attn.prefill_positions": positions,
                "attn.kernel_positions": positions * tiled,
                "mla.layer_steps": int(computed > 0), "mla.kernel_steps": 0,
                "mla.ring_bytes": 4 * self.width * read,
                "cache.latent_bytes": pages * entry.nbytes}


class _LatentAttention(_KindLatent):
    """The latent-attention mixer (MLA) of layer i, unmasked, at the
    MODEL'S heads and rotary base:
    rotary with the spec's `rope_scaling` (YaRN) on ``q_rope`` and ``k_r``
    only; softmax scale `attention_multiplier`; `query_scale`.  The
    full-sequence forms up-project per head and carry a value narrower
    than the key at the key's width (ops/latent.py); the decode step
    absorbs `W_kvb` into the query.  `lora_rescale` as every latent
    kind's."""

    KIND = "latent_attention"
    SIZES, OPTIONS = _KindLatent.SIZES[1:], {"lora_rescale": False}
    NEEDS = ("an even latent_rope_dim, and latent_value_dim within "
             "latent_nope_dim + latent_rope_dim: the up-projected form goes "
             "through _sdp_attention, whose heads have one width, the key's")

    def __init__(self, lm):
        super().__init__(lm)
        self.rope_theta = lm.rope_theta
        # an option appears on a node only when the spec sets it
        scaling = lm.rope_scaling
        if scaling is not None:
            self.rope_attrs["yarn"] = tuple(
                float(scaling[k]) for k in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow"))
            # YaRN's attention factor: the ratio of the two mscales
            grow = 0.1 * math.log(float(scaling["factor"]))
            factor = ((grow * float(scaling.get("mscale", 1.0)) + 1.0)
                      / (grow * float(scaling.get("mscale_all_dim", 0.0))
                         + 1.0))
            if factor != 1.0:
                self.rope_attrs["rope_scale"] = factor
        if lm.attention_multiplier is not None:
            self.attrs["scale"] = lm.attention_multiplier
        if lm.query_scale is not None:
            self.attrs["query_scale"] = lm.query_scale

    def _fits(self, own):
        return super()._fits(own) and (
            own["value_dim"] <= own["nope_dim"] + own["rope_dim"])

    def _project(self, x, p, i, index=None):
        """``(q_nope, q_rope, latent)`` of the normed stream: the rotary
        parts turned — each row's own `index` in a decode step, 0..T-1
        without one — and ``latent = [norm(c_kv) | k_r]``, the row the
        ring keeps."""
        c_q, latent = self._latents(x, p, i, index)
        q_nope, q_rope = self._queries(
            c_q, p, i, index, end=self.heads * (self.nope + self.rope))
        return q_nope, q_rope, latent

    def _expanded(self, x, p, i):
        q_nope, q_rope, latent = self._project(x, p, i)
        ctx = sym._latent_attention(q_nope, q_rope, latent, p["kvb_weight"],
                                    name="l%d_attn" % i, **self.attrs)
        return self._out(ctx, p, i), latent

    def prefill(self, x, p, i, caches, slot, length):
        y, latent = self._expanded(x, p, i)
        return y, [sym._latent_cache_write(
            caches["latent_cache_%d" % i], latent, slot,
            name="l%d_latent_write" % i)]

    def decode(self, x, p, i, caches, slot, length):
        q_nope, q_rope, latent = self._project(x, p, i, index=length)
        step = sym._latent_cached_attention(
            q_nope, q_rope, latent, p["kvb_weight"],
            caches["latent_cache_%d" % i], slot, length,
            name="l%d_attn" % i, **self.attrs)
        return self._out(step[0], p, i), [step[1]]

    def counters(self, i, positions=0, rows=0, lengths=(), computed=0,
                 pages=0, max_len=None, platform=None, **call):
        """What every latent layer adds, with — a prefill's positions
        attend among themselves up-projected — those of them the TPU's
        blockwise kernel takes (``ops.attention.prefill_block``), a decode
        step served by the TPU's kernel where a program lowered for `platform`
        has it, and the bytes of this layer's pages the step reads by the
        kernel's blocks, up to the one that holds each row's `length`, or
        whole pages where the ``jax.numpy`` body runs."""
        _, entry = self.cache_spec(
            i, 1, self.lm.max_len if max_len is None else max_len)[0]
        ring = entry.shape[3]
        block = _attention.decode_block(entry.shape, platform, latent=True)
        at_a_time = block or ring
        read = sum(min((n // at_a_time + 1) * at_a_time, ring)
                   for n in lengths)
        tiled = _attention.prefill_block(
            (1, positions, self.heads * (self.nope + self.rope)), self.heads,
            self.heads, platform) is not None
        counters = self._latent_counters(i, positions, computed, pages,
                                         max_len, read, tiled)
        counters["mla.kernel_steps"] = (counters["mla.layer_steps"]
                                        * (block is not None))
        return counters


class _SparseLatentAttention(_KindLatent):
    """The latent-attention mixer under a LEARNED SELECTION (DeepSeek-
    V3.2's indexer; ops/sparse_latent.py): `index_heads` query heads of
    `index_dim` made from the query latent (``l<i>_iq_weight``), ONE key of
    `index_dim` a position (``l<i>_ik_weight`` and a LayerNorm), weights of
    the stream (``l<i>_iw_weight``, times ``index_heads^-1/2 index_dim^
    -1/2``), rotary on the first `rope_dim` channels of both; row t attends
    to the `index_topk` positions of largest ``sum_j w_j relu(q_j . k_s)``.
    State: the latent ring ``(slots, 1, kv_rank + rope_dim, max_len)`` AND
    the index keys, kind ``"index"``, ``(slots, 1, index_dim, max_len)``.
    A whole sequence keeps the selection as a mask; a decode step scores
    the cached keys, takes the exact top-k and attends, absorbed, to the
    rows gathered there."""

    KIND = "sparse_latent_attention"
    SIZES = _KindLatent.SIZES + ("index_heads", "index_dim", "index_topk")
    NEEDS = "rope_dim even, within index_dim"

    def __init__(self, lm):
        super().__init__(lm)
        self.index_heads, self.index_dim, self.topk = (
            self.sizes[k] for k in self.SIZES[-3:])
        self.index_attrs = dict(index_heads=self.index_heads,
                                top_k=self.topk)

    def _fits(self, own):
        return super()._fits(own) and own["rope_dim"] <= own["index_dim"]

    def params(self, i):
        v, d = sym.Variable, self.lm.d_model
        p = super().params(i)
        p["iq_weight"] = v("l%d_iq_weight" % i, shape=(
            self.index_heads * self.index_dim, self.q_rank))
        p["ik_weight"] = v("l%d_ik_weight" % i, shape=(self.index_dim, d))
        p["iw_weight"] = v("l%d_iw_weight" % i, shape=(self.index_heads, d))
        return p

    def cache_spec(self, i, slots, max_len):
        """The latent ring, kind ``"latent"``, and the index keys, kind
        ``"index"``: both ``(slots, 1, lines, max_len)``, the positions on
        the minor axis."""
        return super().cache_spec(i, slots, max_len) + [
            ("index_cache_%d" % i, CacheEntry(
                "index", (int(slots), 1, self.index_dim, int(max_len))))]

    def _index(self, x, c_q, p, i, index=None):
        """``(index_q, index_k, index_w)``: the indexer's queries and key
        turned over their first `rope_dim` channels, its weights scaled."""
        wide = self.index_heads * self.index_dim
        q = self._turn(self._fc(c_q, p, "iq", wide, "l%d_iq" % i),
                       "l%d_iqrope" % i, self.index_heads, index,
                       rotary_dim=self.rope)
        k = sym.LayerNorm(
            self._fc(x, p, "ik", self.index_dim, "l%d_ik" % i),
            gamma=sym.Variable("l%d_ik_norm_gamma" % i,
                               shape=(self.index_dim,)),
            beta=sym.Variable("l%d_ik_norm_beta" % i,
                              shape=(self.index_dim,)),
            eps=self.lm.norm_eps, name="l%d_ik_norm" % i)
        k = self._turn(k, "l%d_ikrope" % i, 1, index, rotary_dim=self.rope)
        w = self._fc(x, p, "iw", self.index_heads, "l%d_iw" % i) * (
            self.index_heads ** -0.5 * self.index_dim ** -0.5)
        return q, k, w

    def _expanded(self, x, p, i):
        operands, attrs, rows = self._masked_operands(x, p, i)
        index = self._index(x, operands[0], p, i)
        ctx = sym._sparse_latent_attention(
            *operands, *index, name="l%d_attn" % i, **attrs,
            **self.index_attrs)
        return self._out(ctx, p, i), rows + [index[1]]

    def prefill(self, x, p, i, caches, slot, length):
        y, (latent, index_k) = self._expanded(x, p, i)
        return y, [
            sym._latent_cache_write(caches["latent_cache_%d" % i], latent,
                                    slot, name="l%d_latent_write" % i),
            sym._latent_cache_write(caches["index_cache_%d" % i], index_k,
                                    slot, name="l%d_index_write" % i)]

    def decode(self, x, p, i, caches, slot, length):
        c_q, latent = self._latents(x, p, i, index=length)
        q_nope, q_rope = self._queries(c_q, p, i, length)
        step = sym._sparse_latent_cached_attention(
            q_nope, q_rope, latent, p["kvb_weight"],
            *self._index(x, c_q, p, i, index=length),
            caches["latent_cache_%d" % i], caches["index_cache_%d" % i],
            slot, length, name="l%d_attn" % i, **self.attrs,
            **self.index_attrs)
        return self._gated_out(step[0], x, p, i), [step[1], step[2]]

    def counters(self, i, positions=0, rows=0, lengths=(), computed=0,
                 pages=0, max_len=None, platform=None, **call):
        """What a prefill of a bucket of `positions` adds: its positions —
        all of them through the TPU's masked kernel where a program lowered
        for `platform` has it (``ops.sparse_latent.masked_block``) —, its
        causal (query, key) pairs and those of them the selection keeps
        (row t its ``min(t + 1, index_topk)`` best).  What one decode step adds:
        a sparse layer-step, and one on the GATHERED form where the ring
        is longer than `index_topk` (a shorter one is read whole); each
        real row's cached positions and those of them it attends to; the
        bytes of index keys the step scores (whole pages); of the `pages`
        pages bound, this layer's index keys' bytes."""
        ring = self.lm.max_len if max_len is None else int(max_len)
        keys = dict(self.cache_spec(i, 1, ring))["index_cache_%d" % i]
        cached = [n + 1 for n in lengths]
        selected = sum(min(n, self.topk) for n in cached)
        whole = min(positions, self.topk)
        step = int(computed > 0)
        tiled = _sparse_latent.masked_block(
            positions, self.heads, self.nope + self.rope, self.value,
            platform) is not None
        return dict(
            self._latent_counters(i, positions, computed, pages, max_len,
                                  selected, tiled),
            **{"sparse.prefill_pairs": positions * (positions + 1) // 2,
               "sparse.prefill_kept": (whole * (whole + 1) // 2
                                       + (positions - whole) * self.topk),
               "sparse.layer_steps": step,
               "sparse.kernel_steps": step * (ring > self.topk),
               "sparse.context_positions": sum(cached),
               "sparse.selected_positions": selected,
               "sparse.index_bytes": rows * keys.nbytes,
               "cache.index_bytes": pages * keys.nbytes})


class _WindowLatentAttention(_KindLatent):
    """The latent-attention mixer under a sliding window: row i attends to
    ``j <= i`` with ``i - j < window`` (itself and the W - 1 before it).
    State: ONE latent ring of ``min(window, max_len)`` positions whatever
    the session's length — a prefill writes the prompt's last W rows and a
    decode step writes at ``length mod W``, so a ring that is full holds
    exactly the window."""

    KIND = "window_latent_attention"
    SIZES = _KindLatent.SIZES + ("window",)

    def __init__(self, lm):
        super().__init__(lm)
        self.window = self.sizes["window"]

    def cache_spec(self, i, slots, max_len):
        return [("latent_cache_%d" % i, CacheEntry("latent", (
            int(slots), 1, self.width, min(self.window, int(max_len)))))]

    def _expanded(self, x, p, i):
        operands, attrs, rows = self._masked_operands(x, p, i)
        ctx = sym._window_latent_attention(
            *operands, name="l%d_attn" % i, window=self.window, **attrs)
        return self._out(ctx, p, i), rows

    def prefill(self, x, p, i, caches, slot, length):
        y, (latent,) = self._expanded(x, p, i)
        return y, [sym._latent_window_write(
            caches["latent_cache_%d" % i], latent, slot, length,
            name="l%d_latent_write" % i)]

    def decode(self, x, p, i, caches, slot, length):
        c_q, latent = self._latents(x, p, i, index=length)
        q_nope, q_rope = self._queries(c_q, p, i, length)
        step = sym._window_latent_cached_attention(
            q_nope, q_rope, latent, p["kvb_weight"],
            caches["latent_cache_%d" % i], slot, length,
            name="l%d_attn" % i, **self.attrs)
        return self._gated_out(step[0], x, p, i), [step[1]]

    def counters(self, i, positions=0, rows=0, lengths=(), computed=0,
                 pages=0, max_len=None, **call):
        """What every latent layer adds — a step reads each real row's
        whole ring —, and of one decode step: a window row for each real
        row of this layer, those of them whose ring has wrapped (``length
        >= W``), and the bytes of this layer's ring among the `pages`
        pages bound."""
        ring = min(self.window,
                   self.lm.max_len if max_len is None else int(max_len))
        counters = self._latent_counters(i, positions, computed, pages,
                                         max_len, rows * ring)
        return dict(counters, **{
            "kv.window_rows": rows,
            "kv.wrapped_rows": sum(1 for n in lengths if n >= self.window),
            "cache.window_bytes": counters["cache.latent_bytes"]})


class _Recurrent:
    """What the two recurrent kinds share: a fused input projection of
    `d_proj` rows, ONE op node a form (``OPS``: full sequence, prefill,
    decode step) that takes the projection and the mixer's `SMALL`
    parameters (of `small_shapes`) and returns `d_inner` channels, an
    output projection, no bias anywhere; and two kind-``"state"`` entries
    a layer, ``conv_state_<i>`` and ``<STATE>_<i>`` (`state_shapes`: one
    slot's), neither of which grows with `max_len`."""

    def params(self, i):
        v, d = sym.Variable, self.lm.d_model
        p = {"inproj_weight": v("l%d_inproj_weight" % i,
                                shape=(self.d_proj, d))}
        for n, shape in zip(self.SMALL, self.small_shapes):
            p[n] = v("l%d_%s" % (i, n), shape=shape)
        p["outproj_weight"] = v("l%d_outproj_weight" % i,
                                shape=(d, self.d_inner))
        return p

    def cache_spec(self, i, slots, max_len):
        return [("%s_%d" % (name, i), CacheEntry("state",
                                                 (int(slots),) + shape))
                for name, shape in zip(("conv_state", self.STATE),
                                       self.state_shapes)]

    def _in(self, x, p, i):
        proj = sym.FullyConnected(x, weight=p["inproj_weight"],
                                  num_hidden=self.d_proj, no_bias=True,
                                  flatten=False, name="l%d_inproj" % i)
        return [proj] + [p[n] for n in self.SMALL]

    def _out(self, y, p, i):
        return sym.FullyConnected(y, weight=p["outproj_weight"],
                                  num_hidden=self.lm.d_model, no_bias=True,
                                  flatten=False, name="l%d_outproj" % i)

    def _states(self, caches, i):
        return [caches["conv_state_%d" % i],
                caches["%s_%d" % (self.STATE, i)]]

    def _page_bytes(self, i):
        """One slot's window and state of layer i, in bytes."""
        return sum(e.nbytes for _, e in self.cache_spec(i, 1, 0))

    def full(self, x, p, i):
        y = getattr(sym, self.OPS[0])(*self._in(x, p, i),
                                      name="l%d_%s" % (i, self.NODE),
                                      **self.attrs)
        return self._out(y, p, i)

    def _fill(self, operands, states, i, slot, length):
        """A prompt's positions the prefill way: the scan from an empty
        state, the slot's window and state written whole.  Returns (y,
        the two states)."""
        y = getattr(sym, self.OPS[1])(
            *operands, *states, slot, length,
            name="l%d_%s" % (i, self.NODE), **self.attrs)
        return y[0], [y[1], y[2]]

    def _step(self, operands, states, i, slot, tag=""):
        """Packed rows the decode way: one token each on its own slot's
        window and state.  Returns (y, the two states)."""
        y = getattr(sym, self.OPS[2])(
            *operands, *states, slot,
            name="l%d_%s%s" % (i, tag, self.NODE), **self.attrs)
        return y[0], [y[1], y[2]]

    def prefill(self, x, p, i, caches, slot, length):
        y, states = self._fill(self._in(x, p, i), self._states(caches, i),
                               i, slot, length)
        return self._out(y, p, i), states

    def decode(self, x, p, i, caches, slot, length):
        y, states = self._step(self._in(x, p, i), self._states(caches, i),
                               i, slot)
        return self._out(y, p, i), states


class _Mamba2(_Recurrent):
    """The Mamba-2 mixer of layer i (ops/ssm.py has the equations): input
    projection ``[z | x | B | C | dt]``, causal conv + state-space scan +
    gated RMSNorm in ONE op node a form, output projection.  State: the
    conv window ``(slots, d_conv - 1, conv_dim)`` — channels on the
    lanes; stored ``(conv_dim, d_conv - 1)`` a TPU tile would pad the 3
    taps to 128 — and the recurrent state ``(slots, heads, head_dim,
    d_state)``.  Its own, ``kind_specs["mamba"]``: `heads` x `head_dim`
    (its inner width), `state`, `groups`, `conv` taps and the prefill
    scan's `chunk`; the gated norm goes by the same `groups` (``ops/ssm.py``)."""

    KIND = "mamba"
    SIZES = ("heads", "head_dim", "state")
    OPTIONS = {"groups": 1, "conv": 4, "chunk": 256}
    NEEDS = "mamba_heads a multiple of mamba_groups >= 1, mamba_conv >= 2"
    OPS, NODE, STATE = ("_ssm_scan", "_ssm_prefill", "_ssm_step"), "ssm", \
        "ssm_state"
    # the mixer's own small parameters, in the ops' operand order
    SMALL = ("conv_weight", "conv_bias", "dt_bias", "A_log", "D",
             "mnorm_gamma")

    def __init__(self, lm):
        self.lm = lm
        own = _own_sizes(self, lm)
        heads, head_dim, state, groups, conv = sizes = tuple(
            own[k] for k in ("heads", "head_dim", "state", "groups", "conv"))
        self.small_shapes = _ssm.param_shapes(*sizes)
        self.d_inner = heads * head_dim
        conv_dim = self.d_inner + 2 * groups * state
        self.d_proj = self.d_inner + conv_dim + heads
        self.state_shapes = ((conv - 1, conv_dim), sizes[:3])
        self.attrs = dict(num_heads=heads, head_dim=head_dim,
                          state_size=state, n_groups=groups,
                          conv_kernel=conv, chunk_size=own["chunk"],
                          eps=lm.norm_eps)

    def _fits(self, own):
        return (own["groups"] >= 1 and own["heads"] % own["groups"] == 0
                and own["conv"] >= 2)

    def counters(self, i, positions=0, rows=0, platform=None, **call):
        """What one program call adds: the bucket positions a prefill
        scans in this layer (the pad included), the bytes of window and
        state a decode step's `rows` rows read and write, and those of
        them that a program lowered for `platform` moves with the step
        kernel (all, or none where ``ops.ssm.step_heads`` says the
        ``jax.numpy`` body runs)."""
        page = self._page_bytes(i)
        stepped = _ssm.step_heads((1,) + self.state_shapes[1], platform,
                                  self.attrs["n_groups"]) is not None
        return {"ssm.scan_positions": positions,
                "ssm.state_bytes": 2 * rows * page,
                "ssm.step_kernel_bytes": 2 * rows * page * stepped}


class _GatedDeltaNet(_Recurrent):
    """The Gated DeltaNet mixer of layer i (ops/gdn.py has the equations):
    input projection ``[q | k | v | z | b | a]``, causal conv over ``[q | k
    | v]`` + delta rule + gated per-head RMSNorm in ONE op node a form,
    output projection.  Its own, ``kind_specs["linear_attention"]``:
    `heads` VALUE heads (v, z, b, a, a state each) of `key_dim` x
    `value_dim`; q and k have `key_heads` heads, as many unless the spec
    says fewer, which they divide — each then read by a group of value
    heads; `conv` taps, the `chunk` of the full-sequence form, `neg_eigval`
    (``beta`` reaches 2).  State: the conv window ``(slots, taps - 1,
    conv_dim)`` and the delta-rule state ``(slots, key_dim, heads *
    value_dim)`` — the key axis leading, the heads' values side by side
    on the lanes (ops/gdn.py: for heads of 96 x 192 a TPU tile then pads
    nothing)."""

    KIND = "linear_attention"
    SIZES = ("heads", "key_dim", "value_dim")
    OPTIONS = {"conv": 4, "chunk": 64, "neg_eigval": True, "key_heads": 0}
    NEEDS = ("linear_heads a multiple of linear_key_heads >= 1, "
             "linear_conv >= 2")
    OPS, NODE, STATE = ("_gdn_scan", "_gdn_prefill", "_gdn_step"), "gdn", \
        "gdn_state"
    # the mixer's own small parameters, in the ops' operand order
    SMALL = ("conv_weight", "dt_bias", "A_log", "gnorm_gamma")

    def __init__(self, lm):
        self.lm = lm
        own = _own_sizes(self, lm)
        h, dk, dv, hk = self.head_sizes = (
            own["heads"], own["key_dim"], own["value_dim"],
            own["key_heads"] or own["heads"])
        self.small_shapes = _gdn.param_shapes(h, dk, dv, own["conv"], hk)
        self.d_inner = h * dv
        conv_dim = _gdn.conv_channels(h, dk, dv, hk)
        self.d_proj = conv_dim + self.d_inner + 2 * h
        self.state_shapes = ((own["conv"] - 1, conv_dim), (dk, self.d_inner))
        self.attrs = dict(num_heads=h, key_dim=dk, value_dim=dv,
                          conv_kernel=own["conv"], chunk_size=own["chunk"],
                          neg_eigval=own["neg_eigval"], eps=lm.norm_eps)
        if hk != h:   # on a node only when the spec sets it
            self.attrs["num_key_heads"] = hk

    def _fits(self, own):
        key_heads = own["key_heads"] or own["heads"]
        return (key_heads >= 1 and own["heads"] % key_heads == 0
                and own["conv"] >= 2)

    def mixed(self, x, p, i, caches, slot, length, rows):
        """The prefill of one prompt AND the decode step of `rows` packed
        rows around ONE input and ONE output projection: `x` ``(1, T +
        rows.n, d)`` holds the prompt's positions, then the rows' tokens.
        Only the mixer's core splits — the prompt's scan writes slot
        `slot` whole, then the rows step on the states so written."""
        proj, *small = self._in(x, p, i)
        proj, proj_r = _split_rows(proj, rows.n, "l%d_inproj" % i)
        y, states = self._fill([proj] + small, self._states(caches, i), i,
                               slot, length)
        y_r, states = self._step([proj_r] + small, states, i, rows.slot,
                                 tag="row_")
        return self._out(_join_rows(y, y_r, "l%d_mixed" % i), p, i), states

    def counters(self, i, positions=0, rows=0, platform=None, **call):
        """What one program call adds: the bucket positions a prefill
        scans in this layer (the pad included), those of them that a
        program lowered for `platform` runs through the TPU's kernel
        (all, or none where ``ops.gdn.chunk_heads`` says the ``jax.numpy``
        body runs), the bytes of window and state a decode step's
        `rows` rows read and write, and those of them that such a
        program's step kernel moves (all, or none where
        ``ops.gdn.step_heads`` says the body runs)."""
        page = self._page_bytes(i)
        h, dk, dv, hk = self.head_sizes
        tiled = _gdn.chunk_heads((1, positions, h, dk), dv,
                                 self.attrs["chunk_size"], platform,
                                 hk) is not None
        stepped = _gdn.step_heads((1,) + self.state_shapes[1], dv,
                                  platform) is not None
        return {"gdn.scan_positions": positions,
                "gdn.kernel_positions": positions * tiled,
                "gdn.state_bytes": 2 * rows * page,
                "gdn.step_kernel_bytes": 2 * rows * page * stepped}


class _Nothing:
    """The half a layer does not have — kind ``"none"`` of `layer_types`
    or of `ffn_types`: no parameters, no norm, no cache entry, no counters,
    no join.  A layer of ONE sublayer (``h <- h + F(norm(h))`` with one
    norm) names it for its other half; the graph builders pass a half
    that is not there by (`TransformerLM._mixer_half`, `_ffn`)."""

    KIND = "none"

    def __init__(self, lm):
        self.lm = lm

    def params(self, i):
        return {}

    def cache_spec(self, i, slots, max_len):
        return []


_KINDS = {"attention": _Attention, "window_attention": _WindowAttention,
          "mamba": _Mamba2, "linear_attention": _GatedDeltaNet,
          "latent_attention": _LatentAttention,
          "sparse_latent_attention": _SparseLatentAttention,
          "window_latent_attention": _WindowLatentAttention,
          "none": _Nothing}


class _DenseFFN:
    """The dense FFN of layer i: ``W2 relu(W1 x)``, or with
    ``ffn="swiglu"`` ``W2 (silu(a) * b)`` with ``[a | b]`` one fused
    projection, of width `d_ff`."""

    def __init__(self, lm):
        self.lm = lm

    def params(self, i):
        lm, v = self.lm, sym.Variable
        d, ff = lm.d_model, lm.d_ff
        wide = 2 * ff if lm.ffn == "swiglu" else ff
        p = {"ffn1_weight": v("l%d_ffn1_weight" % i, shape=(wide, d)),
             "ffn2_weight": v("l%d_ffn2_weight" % i, shape=(d, ff))}
        if lm.bias:
            p["ffn1_bias"] = v("l%d_ffn1_bias" % i, shape=(wide,))
            p["ffn2_bias"] = v("l%d_ffn2_bias" % i, shape=(d,))
        return p

    def apply(self, x, p, i, loads, mixer_in=None):
        lm = self.lm
        if lm.ffn == "swiglu":
            a, b = sym.SliceChannel(
                lm._linear(x, p, "ffn1", 2 * lm.d_ff, "l%d_ffn1" % i),
                num_outputs=2, axis=2, name="l%d_ffn_split" % i)
            f = sym.Activation(a, act_type="silu", name="l%d_silu" % i) * b
        else:
            f = sym.Activation(
                lm._linear(x, p, "ffn1", lm.d_ff, "l%d_ffn1" % i),
                act_type="relu", name="l%d_gelu" % i)
        return lm._linear(f, p, "ffn2", lm.d_model, "l%d_ffn2" % i)


_LANES = 128


def stored_width(width):
    """The width a routed layer STORES its experts at: `width` rounded up
    to whole 128-lane tiles, the pad ZERO (`_RoutedFFN.stored`) — the same
    numbers exactly (every `expert_act` is 0 at 0, so a zero column of an
    in-projection meets a zero row of `down_weight` with a zero; its
    gradient is zero too, so training keeps it).  A TPU keeps a stack
    ``(experts, d_model, width)`` whose width is no whole number of tiles
    with `d_model` on the lanes instead; XLA's ragged-dot wants the width
    there and COPIES the stack every step of every routed layer (read off
    Nemotron-H's step compiled for a described v5e at 1,856: 639 MB a
    layer, 0.68 GB of temporaries), and `parallel.moe.kernel_tiles` takes
    no call of it.  A width within one tile (the tests' sizes) is left as
    it is; every width of whole tiles — all the accepted decoders' — is
    its own."""
    return width if width <= _LANES else -(-width // _LANES) * _LANES


class _RoutedFFN:
    """The routed FFN of layer i (``mx.sym.MoE``, dropless):
    `experts_per_token` of `num_experts` gated experts (`expert_act`:
    SwiGLU, or a ReLU gate; with `expert_gated` false experts of TWO
    matrices, ``W2 act(W1 x)``, the shared one too — `expert_act`
    ``"relu2"`` squares the ReLU — under the device scope
    ``mx:moe.ungated``) of width `expert_d_ff` (`stored_width`: what the
    stacks are STORED at) by the router's scores
    — of the FFN's own normed input or, `router_input` ``"mixer"``, of what
    the block's mixer read —, plus — `shared_d_ff` — one
    expert every token passes, times — `shared_gate` — the sigmoid of its
    own score ``x w_s``, ``w_s`` the ``(d_model, 1)``
    ``l<i>_shared_score_weight``.  `held_experts` ``(first, count)`` are the
    experts whose matrices this model holds, one chip's share of the
    layer: the router and the choice stay `num_experts` wide — and
    `zero_experts` wider: zero-compute experts behind the real ones, which
    add their weights' sum times the token itself."""

    def __init__(self, lm):
        self.lm = lm
        held = lm.held_experts
        self.held = lm.num_experts if held is None else held[1]
        self.width = stored_width(lm.expert_d_ff)
        self.router_width = lm.num_experts + lm.zero_experts
        # an expert's matrices (each stacked an expert), in the order
        # ``mx.sym.MoE`` takes them: in, out and — gated — the second in
        self.expert_keys = (("gate_weight", "down_weight", "up_weight")
                            if lm.expert_gated else
                            ("up_weight", "down_weight"))
        # beyond OLMoE's: an option appears on a node only when the
        # spec sets it
        self.attrs = {}
        if lm.router_score != "softmax":
            self.attrs["score_func"] = lm.router_score
        if lm.router_bias:
            self.attrs["select_bias"] = True
        if lm.route_scale != 1.0:
            self.attrs["route_scale"] = lm.route_scale
        if lm.shared_d_ff:
            self.attrs["shared_size"] = lm.shared_d_ff
        if lm.shared_gate:
            self.attrs["shared_gate"] = True
        if held is not None:
            self.attrs.update(held_first=held[0], held_count=held[1])
        if lm.router_input == "mixer":
            self.attrs["router_input"] = True
        if lm.zero_experts:
            self.attrs["zero_experts"] = lm.zero_experts

    def params(self, i):
        lm, v = self.lm, sym.Variable
        d, ff, e, s = lm.d_model, self.width, self.held, lm.shared_d_ff
        p = {"router_weight": v("l%d_router_weight" % i,
                                shape=(d, self.router_width))}
        if lm.router_bias:
            p["router_bias"] = v("l%d_router_bias" % i,
                                 shape=(self.router_width,))
        for key in self.expert_keys:
            p[key] = v("l%d_%s" % (i, key), shape=(
                (e, ff, d) if key == "down_weight" else (e, d, ff)))
        for key in self.expert_keys if s else ():
            p["shared_" + key] = v("l%d_shared_%s" % (i, key), shape=(
                (s, d) if key == "down_weight" else (d, s)))
        if lm.shared_gate:
            p["shared_score_weight"] = v("l%d_shared_score_weight" % i,
                                         shape=(d, 1))
        return p

    def apply(self, x, p, i, loads, mixer_in=None):
        lm = self.lm
        operands = [x, p["router_weight"]]
        operands += [p["router_bias"]] if lm.router_bias else []
        operands += [p[key] for key in self.expert_keys]
        if lm.shared_d_ff:
            operands += [p["shared_" + key] for key in self.expert_keys]
        if lm.shared_gate:
            operands.append(p["shared_score_weight"])
        if lm.router_input == "mixer":
            operands.append(mixer_in)
        # (a scope of the node only where the spec has two-matrix experts)
        scope = {} if lm.expert_gated else {"__scope__": "mx:moe.ungated"}
        with AttrScope(**scope):
            f = sym.MoE(*operands, num_experts=lm.num_experts,
                        hidden_size=self.width, k=lm.experts_per_token,
                        act_type=lm.expert_act, gated=lm.expert_gated,
                        no_bias=True, normalize=lm.route_norm,
                        return_load=loads is not None, name="l%d_moe" % i,
                        **self.attrs)
        if loads is None:
            return f
        loads.append(f[1])
        return f[0]

    def stored(self, key, stack):
        """The expert stack `key` (one of `expert_keys`) as the layer's
        graphs take it: `stack` itself at the stored width, a stack of
        the published `expert_d_ff` with ZEROS behind its columns (an
        in-projection's) or rows (`down_weight`'s) up to `width`."""
        axis = 1 if key == "down_weight" else 2
        spare = self.width - stack.shape[axis]
        if not spare:
            return stack
        if stack.shape[axis] != self.lm.expert_d_ff:
            raise ValueError("%s is %d wide: neither expert_d_ff %d nor the "
                             "stored %d" % (key, stack.shape[axis],
                                            self.lm.expert_d_ff, self.width))
        import jax.numpy as jnp

        pad = [(0, 0)] * 3
        pad[axis] = (0, spare)
        return jnp.pad(getattr(stack, "_data", stack), pad)

    def counters(self, i, positions=0, computed=0, **call):
        """What one program call adds: the (token, expert) pairs the
        router made of the rows the program computed — a prefill's bucket
        `positions`, a decode step's `computed` rows, the pad included as
        `moe.pairs` includes it — whichever chip holds the expert."""
        return {"moe.routed_pairs":
                (positions + computed) * self.lm.experts_per_token}


class _ShortcutFFN(_RoutedFFN):
    """Layer i's dense FFN and, FORKED from the same normed input, a
    routed layer whose result is CARRIED past the next layer's mixer and
    FFN and joins the stream at that layer's end (`TransformerLM._ffn`):
    LongCat-Flash's shortcut-connected expert layer — a published layer is
    two layers here, ``("shortcut", "dense")``.  Between fork and join the
    branch depends on nothing the dense path computes.  Parameters: a
    dense FFN's and a routed FFN's, side by side."""

    def __init__(self, lm):
        super().__init__(lm)
        self.dense = _DenseFFN(lm)

    def params(self, i):
        return dict(self.dense.params(i), **super().params(i))

    def apply(self, x, p, i, loads, mixer_in=None):
        return self.dense.apply(x, p, i, loads)

    def branch(self, x, p, i, loads, mixer_in=None):
        """The routed layer of the input `apply` read (device scope
        ``mx:moe.shortcut``): what the NEXT layer's end joins."""
        with AttrScope(__scope__="mx:moe.shortcut"):
            return super().apply(x, p, i, loads, mixer_in)


_FFNS = {"dense": _DenseFFN, "routed": _RoutedFFN, "shortcut": _ShortcutFFN,
         "none": _Nothing}


class TransformerLM:
    """Decoder-only pre-norm transformer LM spec.

    `vocab`: vocabulary size; `num_layers`/`num_heads`/`d_model`: the
    usual; `d_ff` defaults to ``4 * d_model``; `max_len` bounds the
    positions AND the serving KV ring; `dropout` applies to the
    residual branches during training only.

    The block (defaults: the GPT-2/OPT shape): `norm` ``"layer"`` |
    ``"rms"`` (gain only), eps `norm_eps`; `positions` ``"learned"``
    (a table added to the embedding) | ``"rotary"`` (Q and K turned per
    head, rotate-half over the whole head, base `rope_theta`, or with
    `rotary_dim` over the first `rotary_dim` channels of each head alone,
    the rest passing unturned); `qk_norm`
    normalizes the whole Q and K projections (kind `norm`) before the
    heads are split; `num_experts` > 0 replaces the dense ReLU FFN by a
    dropless routed SwiGLU layer — `experts_per_token` of `num_experts`
    experts of width `d_ff`, router softmax scores used unnormalised;
    `bias` false drops every projection bias; `tied_head` false gives
    the head its own ``head_weight (vocab, d_model)``.

    Further choices (defaults: as if absent): `layer_types` — one mixer
    kind a layer, ``"attention"`` | ``"window_attention"`` | ``"mamba"`` |
    ``"linear_attention"`` | ``"latent_attention"`` |
    ``"sparse_latent_attention"`` | ``"window_latent_attention"`` |
    ``"none"`` (a layer that is an FFN alone: no mixer, no norm for one,
    no cache entry)
    (module docstring; default all attention); `block_norm` ``"input"``
    (``h + f(norm(h))``) | ``"output"`` (``h + norm(f(h))``), for both
    halves of every block; `num_kv_heads` K/V heads shared by groups of
    query heads; `positions` ``"none"`` (no position signal at all);
    `ffn` ``"relu"`` | ``"swiglu"`` (``W_out(silu(a) * b)``, ``[a | b]`` one
    fused ``(2 d_ff, d_model)`` projection); `embedding_multiplier` scales
    the embedded tokens, `residual_multiplier` every branch before it
    joins the stream, `attention_multiplier` replaces ``1/sqrt(d_head)``,
    `logits_scaling` divides the logits; `head_dim` — the width of a head
    where it is not ``d_model / num_heads`` (the projections are then
    ``num_heads * head_dim`` wide); `sliding_window` W of the
    ``"window_attention"`` kind (row i attends to ``j <= i`` with ``i - j <
    W``); `positions` may be a dict by attention kind,
    ``{"window_attention": "rotary"}``
    (kinds left out have none; no learned table then); `qk_norm`
    ``"head"`` norms each head of Q and K on its own with one ``(head_dim,)``
    gain; `out_gate` multiplies attention's context by ``sigmoid(x Wg)``,
    `Wg` fused behind ``[q | k | v]``; `block_norm` ``"both"`` norms a
    branch's input AND its output (``<name>`` and ``<name>_post``);
    `ffn_types` — one FFN kind a layer, ``"dense"`` | ``"routed"`` |
    ``"shortcut"`` (a dense FFN, and a routed layer of the same normed
    input whose result joins at the end of the NEXT layer, which is
    therefore never the last) | ``"none"`` (a layer that is a mixer alone;
    a layer with neither half is refused);
    `expert_d_ff` — a routed expert's width (default `d_ff`; a width
    over one 128-lane tile that is no whole number of them is STORED
    rounded up, the pad zero: `stored_width`, `stored_params`);
    `shared_d_ff` — the width of one expert every token passes,
    `shared_gate` multiplies what it adds by ``sigmoid(x w_s)``;
    `router_score` ``"softmax"`` | ``"sigmoid"``; `router_bias` adds
    ``l<i>_router_bias (num_experts,)`` to the scores for the choice only;
    `route_norm` renormalises the chosen scores, `route_scale` multiplies
    them; `held_experts` ``(first, count)`` — the experts whose matrices
    this model holds, one chip's share: the router stays `num_experts`
    wide; `router_input` ``"ffn"`` | ``"mixer"`` — what a routed FFN's
    router scores: the FFN's own normed input, or the normed stream the
    block's MIXER read (the choice of experts is then known before the
    mixer has run); `expert_act` ``"silu"`` | ``"relu"`` | ``"relu2"``
    (``relu(.)^2``) — the routed experts' activation; `expert_gated`
    (default true) — false: the experts and the shared expert are of TWO
    matrices, ``W2 act(W1 x)``, ``l<i>_up_weight`` / ``l<i>_down_weight``
    with no ``gate_weight``; `zero_experts` n — the router is
    ``num_experts + n`` wide, its last n columns zero-compute experts that
    add ``(sum of their weights) * x`` and hold no matrix (a held range is
    over the real ones; ``moe_load`` gains one column, their pairs);
    `rope_scaling` — YaRN's ``{factor,
    original_max_position_embeddings, beta_fast, beta_slow[, mscale,
    mscale_all_dim]}`` for the ``"latent_attention"`` kind's rotary part;
    `query_scale` ``(beta, period)`` — that kind's query at position p
    times ``1 + beta * ln(1 + floor(p / period))``; `nextn` 1 — a
    multi-token-prediction module behind the trunk, the serving graphs'
    draft (module docstring).

    `kind_specs` ``{kind: {size: value}}`` — each mixer kind's OWN sizes
    (every one an int >= 1) and options (with their defaults), checked
    where the kind is built:

    * ``"mamba"`` — `heads` x `head_dim` (its inner width), `state`;
      `groups` 1 (which divide `heads`), `conv` 4 taps, the prefill
      scan's `chunk` 256; the gated norm takes the `groups` runs of
      channels each on its own mean square.  Flat: ``mamba_<size>``.
    * ``"linear_attention"`` — `heads` value heads of `key_dim` x
      `value_dim`; `conv` 4 taps, the `chunk` 64 of its full-sequence
      form, `neg_eigval` True (``beta`` reaches 2), `key_heads` — heads of
      q and k where they are fewer than `heads`, which they divide.
      Flat: ``linear_<size>``.
    * ``"latent_attention"`` — `q_rank` (the query's low-rank width,
      normed between its two projections), `kv_rank` (the cached ``c``'s
      width), `nope_dim` / `rope_dim` (a head's unturned and rotary query
      channels; one rotary key of an even `rope_dim` serves all heads),
      `value_dim` (a head's value width, at most their sum), at the
      model's `num_heads`; its rotary part turned whatever `positions` says;
      `lora_rescale` (the normed latents times ``sqrt(d_model / rank)``).
      Flat: ``latent_<size>``.
    * ``"sparse_latent_attention"`` / ``"window_latent_attention"`` —
      `num_heads`, `q_rank`, `kv_rank`, `nope_dim`, `rope_dim`,
      `value_dim` (a head's key ``nope_dim + rope_dim`` wide, whatever its
      value's width); `rope_theta` 10000, `lora_rescale`, `head_gate`;
      the sparse kind's `index_heads`, `index_dim`, `index_topk`; the
      window kind's `window`.

    A keyword ``<prefix><size>`` of the three flat spellings IS
    ``kind_specs[kind][size]`` (a kind is spelt ONE way: flat keywords
    beside its mapping are a ValueError); any other unknown keyword is a
    TypeError."""

    def __init__(self, vocab, num_layers=2, num_heads=2, d_model=32,
                 d_ff=None, max_len=64, dropout=0.0, norm="layer",
                 norm_eps=1e-5, positions="learned", rope_theta=10000.0,
                 qk_norm=False, num_experts=0, experts_per_token=0,
                 bias=True, tied_head=True, layer_types=None,
                 num_kv_heads=None, ffn="relu", embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=None,
                 logits_scaling=1.0, block_norm="input", head_dim=None,
                 sliding_window=0, out_gate=False, ffn_types=None,
                 expert_d_ff=None, shared_d_ff=0, router_score="softmax",
                 router_bias=False, route_norm=False, route_scale=1.0,
                 held_experts=None, rotary_dim=None, shared_gate=False,
                 rope_scaling=None, query_scale=None, kind_specs=None,
                 nextn=0, router_input="ffn", expert_act="silu",
                 zero_experts=0, expert_gated=True, **flat):
        if int(nextn) not in (0, 1):
            raise ValueError("nextn must be 0 or 1 (ONE draft a step), got %r"
                             % (nextn,))
        if head_dim is None and d_model % num_heads:
            raise ValueError("d_model=%d not divisible by num_heads=%d"
                             % (d_model, num_heads))
        if norm not in ("layer", "rms"):
            raise ValueError("norm must be 'layer' or 'rms', got %r" % norm)
        # one position signal for the model, or one an attention kind
        kind_positions = dict.fromkeys(("attention", "window_attention"),
                                       positions)
        if isinstance(positions, dict):
            kind_positions.update(dict.fromkeys(kind_positions, "none"),
                                  **positions)
            if (set(kind_positions.values()) - {"rotary", "none"}
                    or len(kind_positions) != 2):
                raise ValueError(
                    "positions by kind must map 'attention' / "
                    "'window_attention' to 'rotary' or 'none', got %r"
                    % (positions,))
        elif positions not in ("learned", "rotary", "none"):
            raise ValueError("positions must be 'learned', 'rotary', 'none' "
                             "or a dict by attention kind, got %r"
                             % (positions,))
        if num_experts and not 0 < experts_per_token <= num_experts:
            raise ValueError("experts_per_token=%d must be in 1..%d"
                             % (experts_per_token, num_experts))
        if ffn not in ("relu", "swiglu"):
            raise ValueError("ffn must be 'relu' or 'swiglu', got %r" % ffn)
        if block_norm not in ("input", "output", "both"):
            raise ValueError("block_norm must be 'input', 'output' or "
                             "'both', got %r" % block_norm)
        if qk_norm == "head" and norm != "rms":
            raise ValueError("qk_norm='head' is an RMSNorm: needs norm='rms'")
        if router_score not in ("softmax", "sigmoid"):
            raise ValueError("router_score must be 'softmax' or 'sigmoid', "
                             "got %r" % router_score)
        if held_experts is not None:
            first, count = (int(n) for n in held_experts)
            if not 0 <= first < first + count <= num_experts:
                raise ValueError("held_experts=%r must be (first, count) "
                                 "within num_experts=%d"
                                 % (held_experts, num_experts))
            held_experts = (first, count)
        num_kv_heads = num_heads if num_kv_heads is None else int(num_kv_heads)
        if num_kv_heads < 1 or num_heads % num_kv_heads:
            raise ValueError("num_heads=%d not a multiple of num_kv_heads=%d"
                             % (num_heads, num_kv_heads))
        layer_types = (("attention",) * int(num_layers) if layer_types is None
                       else tuple(layer_types))
        if len(layer_types) != int(num_layers) or set(layer_types) - set(_KINDS):
            raise ValueError("layer_types must name num_layers=%d kinds of %s,"
                             " got %r" % (num_layers, sorted(_KINDS),
                                          layer_types))
        if rope_scaling is not None:
            rope_scaling = dict(rope_scaling)
            missing = {"factor", "original_max_position_embeddings",
                       "beta_fast", "beta_slow"} - set(rope_scaling)
            if missing or rope_scaling.get("rope_type", "yarn") != "yarn":
                raise ValueError("rope_scaling must be YaRN's: factor, "
                                 "original_max_position_embeddings, beta_fast "
                                 "and beta_slow (mscale, mscale_all_dim), got "
                                 "%r" % (rope_scaling,))
        if query_scale is not None:
            query_scale = tuple(float(v) for v in query_scale)
            if len(query_scale) != 2 or query_scale[1] <= 0:
                raise ValueError("query_scale must be (beta, period), got %r"
                                 % (query_scale,))
        if shared_gate and not shared_d_ff:
            raise ValueError("shared_gate needs a shared expert "
                             "(shared_d_ff >= 1)")
        if "window_attention" in layer_types and sliding_window < 1:
            raise ValueError("a 'window_attention' layer needs "
                             "sliding_window >= 1")
        ffn_types = ((("routed" if num_experts else "dense"),)
                     * int(num_layers) if ffn_types is None
                     else tuple(ffn_types))
        if len(ffn_types) != int(num_layers) or set(ffn_types) - set(_FFNS):
            raise ValueError("ffn_types must name num_layers=%d kinds of %s,"
                             " got %r" % (num_layers, sorted(_FFNS),
                                          ffn_types))
        routed = bool({"routed", "shortcut"} & set(ffn_types))
        if routed and not num_experts:
            raise ValueError("a 'routed' or 'shortcut' FFN needs "
                             "num_experts >= 1")
        bare = [i for i, halves in enumerate(zip(layer_types, ffn_types))
                if halves == ("none", "none")]
        if bare:
            raise ValueError("a layer has a mixer, an FFN or both: layers %r "
                             "have neither ('none' in layer_types AND in "
                             "ffn_types)" % (bare,))
        if router_input == "mixer" and any(
                m == "none" and f != "none"
                for m, f in zip(layer_types, ffn_types)):
            raise ValueError("router_input='mixer' scores what the block's "
                             "mixer reads: every layer with an FFN needs a "
                             "mixer, got layer_types %r" % (layer_types,))
        if ffn_types[-1:] == ("shortcut",):
            raise ValueError("a 'shortcut' FFN's branch joins at the end of "
                             "the NEXT layer: the last layer has none")
        if int(zero_experts) < 0 or (zero_experts and not routed):
            raise ValueError("zero_experts=%r are a routed FFN's, >= 0"
                             % (zero_experts,))
        if router_input not in ("ffn", "mixer"):
            raise ValueError("router_input must be 'ffn' or 'mixer', got %r"
                             % (router_input,))
        if expert_act not in ("silu", "relu", "relu2"):
            raise ValueError("expert_act must be 'silu', 'relu' or 'relu2', "
                             "got %r" % (expert_act,))
        for name, value, default in (("router_input", router_input, "ffn"),
                                     ("expert_act", expert_act, "silu"),
                                     ("expert_gated", bool(expert_gated),
                                      True)):
            if value != default and not routed:
                raise ValueError("%s=%r is a routed FFN's: no layer of "
                                 "ffn_types %r is 'routed'"
                                 % (name, value, ffn_types))
        self.vocab = int(vocab)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.d_model = int(d_model)
        self.d_ff = int(d_ff) if d_ff is not None else 4 * self.d_model
        self.head_dim = None if head_dim is None else int(head_dim)
        self.d_head = (self.d_model // self.num_heads if head_dim is None
                       else self.head_dim)
        self.max_len = int(max_len)
        self.dropout = float(dropout)
        self.norm = norm
        self.norm_eps = float(norm_eps)
        self.positions = positions
        self.rope_theta = float(rope_theta)
        self.qk_norm = qk_norm if qk_norm == "head" else bool(qk_norm)
        self.num_experts = int(num_experts)
        self.experts_per_token = int(experts_per_token)
        self.bias = bool(bias)
        self.tied_head = bool(tied_head)
        self.layer_types = layer_types
        self.num_kv_heads = num_kv_heads
        self.ffn = ffn
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = (None if attention_multiplier is None
                                     else float(attention_multiplier))
        self.logits_scaling = float(logits_scaling)
        self.block_norm = block_norm
        self.kind_positions = kind_positions
        self.sliding_window = int(sliding_window)
        self.out_gate = bool(out_gate)
        self.ffn_types = ffn_types
        self.expert_d_ff = self.d_ff if expert_d_ff is None else int(expert_d_ff)
        self.shared_d_ff = int(shared_d_ff)
        self.router_score = router_score
        self.router_bias = bool(router_bias)
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.held_experts = held_experts
        self.router_input = router_input
        self.expert_act = expert_act
        self.expert_gated = bool(expert_gated)
        self.zero_experts = int(zero_experts)
        self.rotary_dim = None if rotary_dim is None else int(rotary_dim)
        self.shared_gate = bool(shared_gate)
        self.rope_scaling = rope_scaling
        self.query_scale = query_scale
        self.kind_specs = {k: dict(v) for k, v in (kind_specs or {}).items()}
        if set(self.kind_specs) - set(_KINDS):
            raise ValueError("kind_specs names kinds of %s, got %r"
                             % (sorted(_KINDS), sorted(self.kind_specs)))
        for name, value in flat.items():
            kind = next((k for k, prefix in _FLAT.items()
                         if name.startswith(prefix)), None)
            size = name[len(_FLAT.get(kind, "")):]
            if kind is None or size not in (*_KINDS[kind].SIZES,
                                            *_KINDS[kind].OPTIONS):
                raise TypeError("TransformerLM.__init__() got an unexpected "
                                "keyword argument %r" % name)
            if kind in (kind_specs or {}):
                raise ValueError("%s beside kind_specs[%r]: a kind's sizes "
                                 "are spelt ONE way" % (name, kind))
            self.kind_specs.setdefault(kind, {})[size] = value
        if self.rotary_dim is not None and (
                self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.d_head):
            raise ValueError("rotary_dim=%d must be even and within the "
                             "head's %d channels"
                             % (self.rotary_dim, self.d_head))
        self.nextn = int(nextn)
        kinds = {k: _KINDS[k](self) for k in set(layer_types)}
        self._mixers = [kinds[k] for k in layer_types]
        kinds = {k: _FFNS[k](self) for k in set(ffn_types)}
        self._ffns = [kinds[k] for k in ffn_types]
        # the draft module's block, layer `num_layers`: the last layer's
        # kinds (it is not among `_mixers` / `_ffns`: the trunk's loops
        # pass it by)
        self._draft = ([(self.num_layers, self._mixers[-1], self._ffns[-1])]
                       if self.nextn else [])
        self._weights = None   # `step_weight_bytes`' constants

    # ------------------------------------------------------------------
    # shared pieces
    # ------------------------------------------------------------------
    def _embed_weight(self):
        return sym.Variable("embed_weight",
                            shape=(self.vocab, self.d_model))

    def _pos_weight(self):
        return sym.Variable("pos_weight", shape=(self.max_len, self.d_model))

    def _norm(self, x, name, width=None):
        """The model's norm as node `name`, with the parameters
        ``<name>_gamma`` (and ``<name>_beta`` for LayerNorm) of `width`
        (default `d_model`) channels."""
        shape = (self.d_model if width is None else width,)
        gamma = sym.Variable(name + "_gamma", shape=shape)
        if self.norm == "rms":
            return sym.RMSNorm(x, gamma=gamma, eps=self.norm_eps, name=name)
        beta = sym.Variable(name + "_beta", shape=shape)
        return sym.LayerNorm(x, gamma=gamma, beta=beta, name=name)

    def _linear(self, x, p, key, num_hidden, name):
        if self.bias:
            return sym.FullyConnected(
                x, weight=p[key + "_weight"], bias=p[key + "_bias"],
                num_hidden=num_hidden, flatten=False, name=name)
        return sym.FullyConnected(x, weight=p[key + "_weight"],
                                  num_hidden=num_hidden, no_bias=True,
                                  flatten=False, name=name)

    def _layers(self):
        """``(i, mixer, ffn)`` of the trunk's layers."""
        return list(zip(range(self.num_layers), self._mixers, self._ffns))

    def _block_params(self, i, mixer=None, ffn=None):
        """Layer i's parameter variables: its mixer's, then its FFN's."""
        p = (mixer or self._mixers[i]).params(i)
        p.update((ffn or self._ffns[i]).params(i))
        return p

    def _branch_in(self, h, name):
        """What a branch (mixer or FFN) reads of the stream `h`: its norm
        `name`, or — where the block norms the branch's output only — `h`."""
        return h if self.block_norm == "output" else self._norm(h, name)

    def _branch_out(self, y, name):
        """What a branch adds to the stream: `y`, or its norm — `name`
        where the block norms outputs only, ``<name>_post`` where it norms
        both ends of a branch."""
        if self.block_norm == "input":
            return y
        return self._norm(y, name + "_post" * (self.block_norm == "both"))

    def _join(self, h, branch):
        """The residual stream plus a branch (times `residual_multiplier`)."""
        if self.residual_multiplier != 1.0:
            branch = branch * self.residual_multiplier
        return h + branch

    def _ffn(self, h, p, i, train, loads=None, ffn=None, mixer_in=None,
             carried=None):
        """The block's second half on the residual stream `h`: layer i's
        FFN kind between the block's norms.  A routed model's serving
        graphs pass `loads`, which collects each routed layer's
        tokens-per-expert output; `mixer_in` is what the block's mixer
        read, for a router that reads it too (`router_input`).  `carried`
        is the graph's list of branches that wait for their join: the one
        the layer before forked (a ``"shortcut"`` FFN's) joins here, as it
        is, and this layer's own is left there for the next."""
        ffn = ffn or self._ffns[i]
        if isinstance(ffn, _Nothing):
            return self._join(h, carried.pop()) if carried else h
        x = self._branch_in(h, "l%d_ln2" % i)
        f = ffn.apply(x, p, i, loads, mixer_in)
        f = self._branch_out(f, "l%d_ln2" % i)
        if train and self.dropout > 0:
            f = sym.Dropout(f, p=self.dropout, name="l%d_drop" % i)
        h = self._join(h, f)
        if carried:
            h = self._join(h, carried.pop())
        if hasattr(ffn, "branch"):
            carried.append(ffn.branch(x, p, i, loads, mixer_in))
        return h

    def _mixer_half(self, h, mixer, i, mix, train=False):
        """The block's first half on the residual stream `h`: layer i's
        mixer between the block's norms, called through ``mix(x)`` → (its
        output, its cache entries).  Returns (the stream, what the mixer
        read, the entries) — `h` as it came, None and nothing for a layer
        that has no mixer."""
        if isinstance(mixer, _Nothing):
            return h, None, []
        x = self._branch_in(h, "l%d_ln1" % i)
        y, state = mix(x)
        a = self._branch_out(y, "l%d_ln1" % i)
        if train and self.dropout > 0:
            a = sym.Dropout(a, p=self.dropout, name="l%d_adrop" % i)
        return self._join(h, a), x, state

    def _block_train(self, h, i, train, carried):
        p = self._block_params(i)
        mixer = self._mixers[i]
        h, x, _ = self._mixer_half(
            h, mixer, i, lambda x: (mixer.full(x, p, i), []), train)
        return self._ffn(h, p, i, train, mixer_in=x, carried=carried)

    def _embed(self, data, index=None, tables=None, tag=""):
        """Token embedding (plus the learned position table's rows: each
        row's own `index` in a decode step, 0..T-1 without one).  A graph
        that embeds twice hands the second call the `tables` the first
        returned and a `tag` for its nodes' names.  Returns (hidden,
        (embed_weight, pos_weight or None))."""
        embed_w, pos_w = tables or (
            self._embed_weight(),
            self._pos_weight() if self.positions == "learned" else None)
        h = sym.Embedding(data, weight=embed_w, input_dim=self.vocab,
                          output_dim=self.d_model, name=tag + "embed")
        if self.embedding_multiplier != 1.0:
            h = h * self.embedding_multiplier
        if pos_w is not None and index is None:
            h = sym._add_positional(h, pos_w, name=tag + "pos_add")
        elif pos_w is not None:
            h = sym._add_positional_at(h, pos_w, index, name=tag + "pos_add")
        return h, (embed_w, pos_w)

    def _trunk(self, data, train):
        """Embedding + positions + the block stack + final norm; returns
        hidden states ``(N, T, d_model)``."""
        h, (embed_w, _) = self._embed(data)
        carried = []
        for i in range(self.num_layers):
            h = self._block_train(h, i, train, carried)
        return self._norm(h, "ln_f"), embed_w

    def _head_weight(self):
        """An untied head's own matrix (None for a tied one)."""
        return None if self.tied_head else sym.Variable(
            "head_weight", shape=(self.vocab, self.d_model))

    def _head(self, h2d, embed_w, name, weight=None):
        """LM head over flattened positions: ``h @ W^T`` with W the
        embedding table (the tie halves head params and is the reference
        transformer-LM convention) or the head's own matrix (`weight`:
        the variable a graph's first head made, for its second)."""
        w = weight if weight is not None else (
            embed_w if self.tied_head else self._head_weight())
        if self.logits_scaling == 1.0:
            return sym.dot(h2d, w, transpose_b=True, name=name)
        raw = sym.dot(h2d, w, transpose_b=True, name=name + "_unscaled")
        return sym._div_scalar(raw, scalar=self.logits_scaling, name=name)

    def _serving_outputs(self, logits, rings, loads, last_token, slot,
                         sampled=None):
        """``[logits, rings..., last_token, token, moe_load]``: what is
        threaded from call to call (the rings, then the last sampled
        token of every slot), then what the batcher reads — the greedy
        token of each row (`sampled`: a drafting graph's own ``(token,
        last_token')``) and, for a routed model, tokens per (layer,
        expert) of this call."""
        if sampled is None:
            sampled = sym._greedy_token(logits, last_token, slot,
                                        name="token")
        extra = []
        if loads:
            # each layer's held experts and, behind them, ONE entry for
            # all its zero-compute experts
            held = ((self.held_experts or (0, self.num_experts))[1]
                    + bool(self.zero_experts))
            extra = [sym.Reshape(sym.Concat(*loads, dim=0),
                                 shape=(len(loads), held), name="moe_load")]
        return sym.Group([logits] + rings + [sampled[1], sampled[0]] + extra)

    def _routed(self):
        return any(isinstance(ffn, _RoutedFFN) for ffn in self._ffns)

    def extra_outputs(self):
        """Names of the serving graphs' outputs after the token."""
        return ("moe_load",) if self._routed() else ()

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def sym_gen(self, invalid_label=-1):
        """BucketingModule factory: ``f(seq_len) -> (loss_sym,
        data_names, label_names)``.  The graph itself is length-
        independent; seq_len only feeds the iterator's provide_data, so
        every bucket shares these node names and the arg list (the
        BucketingModule shared-param contract)."""

        def _gen(seq_len):
            data = sym.Variable("data")
            label = sym.Variable("softmax_label")
            h, embed_w = self._trunk(data, train=True)
            flat = sym.Reshape(h, shape=(-1, self.d_model), name="flat")
            logits = self._head(flat, embed_w, "logits")
            lab = sym.Reshape(label, shape=(-1,), name="label_flat")
            out = sym.SoftmaxOutput(logits, lab, use_ignore=True,
                                    ignore_label=invalid_label,
                                    normalization="valid", name="softmax")
            return out, ("data",), ("softmax_label",)

        return _gen

    def training_symbol(self, invalid_label=-1):
        net, _, _ = self.sym_gen(invalid_label)(None)
        return net

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def score_symbol(self):
        """Raw per-position logits ``(N*T, vocab)`` (reshape to
        ``(N, T, V)`` host-side) — the full-recompute decode reference:
        step t's next-token logits are row ``t`` of this forward run
        over the first ``t+1`` tokens."""
        data = sym.Variable("data")
        h, embed_w = self._trunk(data, train=False)
        flat = sym.Reshape(h, shape=(-1, self.d_model), name="flat")
        return self._head(flat, embed_w, "logits")

    def cache_spec(self, slots, max_len=None):
        """What a serving session holds on the device between calls, for
        `slots` pages and rings of `max_len` positions (default: the
        model's own): an ordered ``{name: CacheEntry(kind, shape)}`` over
        all layers, each layer's entries as its mixer kind declares them
        (module docstring).  The serving graphs' state inputs and outputs
        are exactly these names in this order; serving/decode.py (which
        adds the +1 scratch slot), the server's admission and chip_smoke
        ask here, and the ops read and write exactly these shapes."""
        max_len = self.max_len if max_len is None else int(max_len)
        spec = {}
        for i, mixer, _ in self._layers() + self._draft:
            spec.update(mixer.cache_spec(i, slots, max_len))
        return spec

    def token_state(self, slots):
        """The shape of ``last_token`` for `slots` pages: one token a slot
        or, for a model that drafts, token, draft and position."""
        return (3, int(slots)) if self.nextn else (int(slots),)

    def call_counters(self, **call):
        """The telemetry counters that ONE serving program call adds to
        beyond the session's own, ``{name: increment}`` summed over the
        layers whose mixer or FFN kind declares any.  `call` says what the
        call was, and a kind reads what concerns it: a prefill of a bucket
        of `positions`; or a decode step of `rows` real rows at `lengths`
        (each row's positions cached before the step) in a program of
        `computed` rows (the pad included), with `pages` pages of cache
        bound on the device (bound sets x slots) for sessions of
        `max_len`; either of a program lowered for `platform` (the
        session's device's).  The session books them at dispatch."""
        total = {}
        for i, *kinds in self._layers() + self._draft:
            for kind in kinds:
                if hasattr(kind, "counters"):
                    for name, n in kind.counters(i, **call).items():
                        total[name] = total.get(name, 0) + n
        return total

    def expert_plan(self, tokens, platform="tpu"):
        """``(pairs, pieces, rows, kernel, fused, placed)`` of each routed
        layer in a serving program that computes `tokens` tokens (the pad
        included):
        the (token, expert) pairs its router makes and how the layer goes
        through them — `parallel.moe.pass_plan`'s pieces and the sorted
        rows a pass gathers, 0 where it gathers every pair's row (no held
        range, or few pairs) —, whether a program lowered for
        `platform` multiplies those rows in the TPU's grouped-matmul
        kernel (`parallel.moe.kernel_tiles` of a call's rows: the three
        matmuls of a layer choose alike), and whether the layer's calls
        also fetch and place their own rows (`parallel.moe.fused_tile`:
        every expert held — these FFNs have no bias), or whether
        its passes return their rows to token order through the TPU's
        kernel (`parallel.moe.return_tiles`: a pass of a held range whose
        width the kernel's tiling holds).  The session keeps it a bucket
        program and books `moe.pair_rows` / `moe.passes` /
        `moe.kernel_rows` / `moe.fused_rows` / `moe.placed_rows` from it
        and the call's ``moe_load``."""
        from ..parallel import moe

        k = self.experts_per_token
        scored = self.num_experts + self.zero_experts
        held = moe.held_range(self.held_experts, scored, self.zero_experts)
        pieces, rows = moe.pass_plan(tokens, k, 4 * self.d_model, held,
                                     scored)
        call = (rows or tokens // pieces * k,
                (held or (0, self.num_experts))[1], self.d_model,
                stored_width(self.expert_d_ff))
        kernel = platform == "tpu" and moe.kernel_tiles(*call) is not None
        fused = (kernel and held is None
                 and moe.fused_tile(*call, self.expert_gated) is not None)
        placed = bool(platform == "tpu" and rows) and moe.return_tiles(
            tokens // pieces, rows, self.d_model, "float32") is not None
        return tokens * k, pieces, rows, kernel, fused, placed

    def step_weight_bytes(self, load=None):
        """``{"mtp.bytes", "mtp.step_bytes"}``: the float32 bytes of the
        WEIGHTS one decode step of a drafting model reads for its draft
        module (``mtp_eh_weight``, its block, the head a second time) and
        for the whole step (every layer's matrices, the head twice; an
        embedding's rows and the norms' gains are left out).  A routed
        layer's experts count where they got a row: `load` ``(routed
        layers, held)`` is the call's ``moe_load``, the module's layer
        last; without it every held expert counts."""
        if self._weights is None:
            fixed, expert = [], []
            for i, mixer, ffn in self._layers() + self._draft:
                sizes = {k: math.prod(ast.literal_eval(v.attr("__shape__")))
                         for k, v in self._block_params(i, mixer,
                                                        ffn).items()}
                routed = isinstance(ffn, _RoutedFFN)
                mine = (sum(sizes[k] for k in ffn.expert_keys) if routed
                        else 0)
                fixed.append(4 * (sum(sizes.values()) - mine))
                expert.append(4 * mine // ffn.held if routed else None)
            self._weights = (fixed, expert,
                             4 * self.vocab * self.d_model,
                             4 * 2 * self.d_model * self.d_model)
        fixed, expert, head, join = self._weights
        if load is not None and self.zero_experts:
            load = load[..., :-1]   # (the zero-compute experts' column)
        hit = iter([] if load is None else (load > 0).sum(axis=-1))
        held = (self.held_experts or (0, self.num_experts))[1]
        layers = [f + (0 if e is None else e * int(next(hit, held)))
                  for f, e in zip(fixed, expert)]
        module = layers[-1] + join + head
        return {"mtp.bytes": module,
                "mtp.step_bytes": sum(layers[:-1]) + head + module}

    def stored_params(self, params):
        """`params` (name -> array) as this model's graphs take them: a
        routed layer's expert stacks of the published `expert_d_ff` padded
        to the width the layer stores (`stored_width`), every other array
        — a stack that is stored already among them — the object it was.
        `GenerativeSession` passes what it is handed through here; what
        trains or scores a checkpoint by `training_symbol` /
        `score_symbol` does the same (an initializer that FILLS the pad
        trains experts of the stored width)."""
        out = dict(params)
        for i, _, ffn in self._layers() + self._draft:
            for key in getattr(ffn, "expert_keys", ()):
                name = "l%d_%s" % (i, key)
                if name in out:
                    out[name] = ffn.stored(key, out[name])
        return out

    def _cache_vars(self):
        return {n: sym.Variable(n) for n in self.cache_spec(1)}

    def _blocks(self, h, mix):
        """The block stack of a serving graph on the stream `h`, layer
        i's mixer called through ``mix(mixer, x, p, i)`` → (its output,
        its cache entries).  Returns (the stream, every layer's entries
        in `cache_spec`'s order, the routed layers' loads or None)."""
        outs, loads = [], [] if self._routed() else None
        carried = []
        for layer in self._layers():
            h = self._block(h, layer, mix, outs, loads, carried)
        return h, outs, loads

    def _block(self, h, layer, mix, outs, loads, carried=None):
        """One block of a serving graph: `layer` ``(i, mixer, ffn)``; its
        cache entries go to `outs`, a routed FFN's load to `loads`, a
        branch it forks for the next block to `carried`."""
        i, mixer, ffn = layer
        p = self._block_params(i, mixer, ffn)
        h, x, state = self._mixer_half(h, mixer, i,
                                     lambda x: mix(mixer, x, p, i))
        outs += state
        return self._ffn(h, p, i, train=False, loads=loads, ffn=ffn,
                         mixer_in=x, carried=carried)

    def _drafted(self, stream, tokens, embed_w, mix, outs, loads):
        """The draft module on the trunk's last `stream` (before ``ln_f``)
        and the `tokens` that FOLLOW its positions: ``[RMS_e(Emb(token)) ;
        RMS_h(stream)] W_eh`` through the module's block — `mix` as
        `_blocks` takes it, its cache entries after the trunk's in `outs`
        — and the head norm: the stream its logits are made of."""
        with AttrScope(__scope__="mx:mtp.embed_join"):
            e = sym.Embedding(tokens, weight=embed_w, input_dim=self.vocab,
                              output_dim=self.d_model, name="mtp_embed")
            if self.embedding_multiplier != 1.0:
                e = e * self.embedding_multiplier
            u = sym.FullyConnected(
                sym.Concat(self._norm(e, "mtp_enorm"),
                           self._norm(stream, "mtp_hnorm"), dim=2,
                           name="mtp_joined"),
                weight=sym.Variable("mtp_eh_weight", shape=(
                    self.d_model, 2 * self.d_model)),
                num_hidden=self.d_model, no_bias=True, flatten=False,
                name="mtp_eh")
        with AttrScope(__scope__="mx:mtp.block"):
            z = self._block(u, self._draft[0], mix, outs, loads)
            return self._norm(z, "mtp_ln_f")

    def prefill_symbol(self):
        """Prefill one prompt (batch 1, padded to a sequence bucket):
        outputs ``[next_logits (1, vocab), <cache_spec entries>'...,
        last_token', token (1,)]``.  Inputs beyond the cache entries:
        ``data (1, T)``, ``slot (1,)``, ``length (1,)`` (true prompt
        length), ``last_token (slots + 1,)``."""
        data = sym.Variable("data")
        slot = sym.Variable("slot")
        length = sym.Variable("length")
        last_token = sym.Variable("last_token")
        caches = self._cache_vars()
        h, (embed_w, _) = self._embed(data)
        h, outs, loads = self._blocks(
            h, lambda mixer, x, p, i: mixer.prefill(x, p, i, caches, slot,
                                                    length))
        if self.nextn:
            return self._prefill_drafting(data, slot, length, last_token,
                                          caches, h, embed_w, outs, loads)
        h = self._norm(h, "ln_f")
        # logits at the prompt's true tail, not the pad
        last = sym._take_step(h, length - 1, name="last_h")
        logits = self._head(last, embed_w, "next_logits")
        return self._serving_outputs(logits, outs, loads, last_token, slot)

    def _prefill_drafting(self, data, slot, length, last_token, caches,
                          stream, embed_w, outs, loads):
        """The prefill's end for a model that drafts: the first token from
        the prompt's tail, then the module over the whole prompt — its
        tokens shifted by one, the first token at the tail —, whose own
        cache entries the prompt fills, and the first draft from ITS tail.
        ``logits (2, vocab)``: the trunk's, then the module's."""
        head = self._head_weight()
        last = sym._take_step(self._norm(stream, "ln_f"), length - 1,
                              name="last_h")
        logits = self._head(last, embed_w, "next_logits", head)
        follows = sym._draft_shift(data, logits, length, name="mtp_tokens")
        z = self._drafted(
            stream, follows, embed_w,
            lambda mixer, x, p, i: mixer.prefill(x, p, i, caches, slot,
                                                 length), outs, loads)
        draft_logits = self._head(
            sym._take_step(z, length - 1, name="mtp_last_h"), embed_w,
            "draft_logits", head)
        sampled = sym._draft_start(logits, draft_logits, length, last_token,
                                   slot, name="token")
        return self._serving_outputs(
            sym.Concat(logits, draft_logits, dim=0, name="all_logits"), outs,
            loads, last_token, slot, sampled=sampled)

    def _decode_drafting(self):
        """`decode_symbol` of a model that drafts (module docstring):
        ``data (B, 1)``, ``slot (B,)``, ``length (B,)`` as the plain
        step's; ``last_token (3, slots + 1)``.  The trunk runs the 2B rows
        ``_draft_feed`` makes (the B verified tokens at their positions,
        then the B drafts one position on: a session's second row attends
        the row its first has just written, every ring's rows being
        written before any is read); ``_draft_verify`` compares; the
        module runs the same 2B rows on the trunk's streams and the
        tokens sampled after them; the next draft is the argmax at each
        session's last valid row.  Outputs ``[logits (3B, vocab): the
        trunk's first rows, its second rows, the module's chosen rows;
        <cache_spec entries>'..., last_token', token (B, 3) = [count,
        first, second]]``."""
        slot = sym.Variable("slot")
        last_token = sym.Variable("last_token")
        caches = self._cache_vars()
        fed = sym._draft_feed(sym.Variable("data"), sym.Variable("length"),
                              last_token, slot, name="token_feed")
        tokens, slots, lengths = fed[0], fed[1], fed[2]
        h, (embed_w, _) = self._embed(tokens, index=lengths)

        def mix(mixer, x, p, i):
            return mixer.decode(x, p, i, caches, slots, lengths)

        h, outs, loads = self._blocks(h, mix)
        head = self._head_weight()
        flat = sym.Reshape(self._norm(h, "ln_f"), shape=(-1, self.d_model),
                           name="flat")
        logits = self._head(flat, embed_w, "next_logits", head)
        verdict = sym._draft_verify(logits, tokens, name="mtp_verify")
        z = self._drafted(h, verdict[0], embed_w, mix, outs, loads)
        chosen = sym._draft_select(z, verdict[1], name="mtp_last_h")
        draft_logits = self._head(chosen, embed_w, "draft_logits", head)
        sampled = sym._draft_commit(verdict[0], verdict[1], draft_logits,
                                    lengths, last_token, slot, name="token")
        return self._serving_outputs(
            sym.Concat(logits, draft_logits, dim=0, name="all_logits"), outs,
            loads, last_token, slot, sampled=sampled)

    def decode_symbol(self):
        """One decode step for a packed session batch: inputs ``data
        (B, 1)`` (each session's last token, or a negative number for
        "the one ``last_token[slot]`` holds"), ``slot (B,)``, ``length
        (B,)`` (tokens already cached), plus the cache entries and
        ``last_token (slots + 1,)``; outputs ``[logits (B, vocab),
        <cache_spec entries>'..., last_token', token (B,)]``."""
        if self.nextn:
            return self._decode_drafting()
        data = sym.Variable("data")
        slot = sym.Variable("slot")
        length = sym.Variable("length")
        last_token = sym.Variable("last_token")
        caches = self._cache_vars()
        data = sym._token_feed(data, last_token, slot, name="token_feed")
        h, (embed_w, _) = self._embed(data, index=length)
        h, outs, loads = self._blocks(
            h, lambda mixer, x, p, i: mixer.decode(x, p, i, caches, slot,
                                                   length))
        h = self._norm(h, "ln_f")
        flat = sym.Reshape(h, shape=(-1, self.d_model), name="flat")
        logits = self._head(flat, embed_w, "next_logits")
        return self._serving_outputs(logits, outs, loads, last_token, slot)

    def mixed_symbol(self, rows):
        """A MIXED STEP: the prefill of one prompt (as `prefill_symbol`)
        and one decode step of `rows` packed rows (as `decode_symbol`) in
        ONE program that reads every weight once — the prompt's ``T``
        positions and the rows' tokens ride every dense product
        (embedding, the mixers' projections, the FFN and its router, the
        final norm and the head) as ONE ``(1, T + rows, d)`` stream, the
        prompt first; only the mixers' cores split (each kind's `mixed`).
        Inputs: the prompt's ``data (1, T)``, ``slot (1,)``, ``length
        (1,)``; the rows' ``row_data (rows, 1)``, ``row_slot (rows,)``,
        ``row_length (rows,)`` (rows with none to serve point at the
        scratch slot with length 0); the cache entries and ``last_token
        (slots + 1,)``.  Outputs as the two graphs', the prompt's row
        first: ``[logits (1 + rows, vocab), <cache_spec entries>'...,
        last_token', token (1 + rows,)]``.  None for a model with a mixer
        kind that has no `mixed` (it keeps the two programs)."""
        if self.nextn or not all(hasattr(mixer, "mixed")
                                 for mixer in self._mixers
                                 if not isinstance(mixer, _Nothing)):
            return None   # (a draft under a mixed step: ROADMAP R10)
        data = sym.Variable("data")
        slot = sym.Variable("slot")
        length = sym.Variable("length")
        last_token = sym.Variable("last_token")
        riders = _Rows(int(rows), sym.Variable("row_slot"),
                       sym.Variable("row_length"))
        caches = self._cache_vars()
        fed = sym._token_feed(sym.Variable("row_data"), last_token,
                              riders.slot, name="token_feed")
        h, tables = self._embed(data)
        h_r, _ = self._embed(fed, index=riders.length, tables=tables,
                             tag="row_")
        h, outs, loads = self._blocks(
            _join_rows(h, h_r, "stream"),
            lambda mixer, x, p, i: mixer.mixed(x, p, i, caches, slot,
                                               length, riders))
        # the prompt's true tail and the rows' tokens: 1 + rows rows of
        # final norm and head
        h, h_r = _split_rows(h, riders.n, "tail")
        last = sym._take_step(h, length - 1, name="last_h")
        flat = sym.Concat(last, sym.Reshape(h_r, shape=(-1, self.d_model),
                                            name="row_flat"),
                          dim=0, name="flat")
        logits = self._head(self._norm(flat, "ln_f"), tables[0],
                            "next_logits")
        return self._serving_outputs(
            logits, outs, loads, last_token,
            sym.Concat(slot, riders.slot, dim=0, name="slots"))
