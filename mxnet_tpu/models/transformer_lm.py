"""Transformer language model — the first transformer in the zoo
(ROADMAP item 2: "modern traffic" for the serving tier, and the first
real SP-runtime consumer outside dryrun).

One :class:`TransformerLM` spec builds FOUR graphs over ONE parameter
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
together are OLMoE's.  The helper methods branch; the four graph
builders are shared.  A routed model's serving graphs end with one
more small output, ``moe_load (num_layers, num_experts)`` — tokens per
expert in this call — which the batcher books as the ``moe.*`` counters.
"""
from __future__ import annotations

from .. import symbol as sym

__all__ = ["TransformerLM"]


class TransformerLM:
    """Decoder-only pre-norm transformer LM spec.

    `vocab`: vocabulary size; `num_layers`/`num_heads`/`d_model`: the
    usual; `d_ff` defaults to ``4 * d_model``; `max_len` bounds the
    positions AND the serving KV ring; `dropout` applies to the
    residual branches during training only.

    The block (defaults: the GPT-2/OPT shape): `norm` ``"layer"`` |
    ``"rms"`` (gain only), eps `norm_eps`; `positions` ``"learned"``
    (a table added to the embedding) | ``"rotary"`` (Q and K turned per
    head, rotate-half over the whole head, base `rope_theta`); `qk_norm`
    normalizes the whole Q and K projections (kind `norm`) before the
    heads are split; `num_experts` > 0 replaces the dense ReLU FFN by a
    dropless routed SwiGLU layer — `experts_per_token` of `num_experts`
    experts of width `d_ff`, router softmax scores used unnormalised;
    `bias` false drops every projection bias; `tied_head` false gives
    the head its own ``head_weight (vocab, d_model)``."""

    def __init__(self, vocab, num_layers=2, num_heads=2, d_model=32,
                 d_ff=None, max_len=64, dropout=0.0, norm="layer",
                 norm_eps=1e-5, positions="learned", rope_theta=10000.0,
                 qk_norm=False, num_experts=0, experts_per_token=0,
                 bias=True, tied_head=True):
        if d_model % num_heads:
            raise ValueError("d_model=%d not divisible by num_heads=%d"
                             % (d_model, num_heads))
        if norm not in ("layer", "rms"):
            raise ValueError("norm must be 'layer' or 'rms', got %r" % norm)
        if positions not in ("learned", "rotary"):
            raise ValueError("positions must be 'learned' or 'rotary', "
                             "got %r" % positions)
        if num_experts and not 0 < experts_per_token <= num_experts:
            raise ValueError("experts_per_token=%d must be in 1..%d"
                             % (experts_per_token, num_experts))
        self.vocab = int(vocab)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.d_model = int(d_model)
        self.d_ff = int(d_ff) if d_ff is not None else 4 * self.d_model
        self.d_head = self.d_model // self.num_heads
        self.max_len = int(max_len)
        self.dropout = float(dropout)
        self.norm = norm
        self.norm_eps = float(norm_eps)
        self.positions = positions
        self.rope_theta = float(rope_theta)
        self.qk_norm = bool(qk_norm)
        self.num_experts = int(num_experts)
        self.experts_per_token = int(experts_per_token)
        self.bias = bool(bias)
        self.tied_head = bool(tied_head)

    # ------------------------------------------------------------------
    # shared pieces
    # ------------------------------------------------------------------
    def _embed_weight(self):
        return sym.Variable("embed_weight",
                            shape=(self.vocab, self.d_model))

    def _pos_weight(self):
        return sym.Variable("pos_weight", shape=(self.max_len, self.d_model))

    def _norm(self, x, name):
        """The model's norm as node `name`, with the parameters
        ``<name>_gamma`` (and ``<name>_beta`` for LayerNorm)."""
        gamma = sym.Variable(name + "_gamma", shape=(self.d_model,))
        if self.norm == "rms":
            return sym.RMSNorm(x, gamma=gamma, eps=self.norm_eps, name=name)
        beta = sym.Variable(name + "_beta", shape=(self.d_model,))
        return sym.LayerNorm(x, gamma=gamma, beta=beta, name=name)

    def _linear(self, x, p, key, num_hidden, name):
        if self.bias:
            return sym.FullyConnected(
                x, weight=p[key + "_weight"], bias=p[key + "_bias"],
                num_hidden=num_hidden, flatten=False, name=name)
        return sym.FullyConnected(x, weight=p[key + "_weight"],
                                  num_hidden=num_hidden, no_bias=True,
                                  flatten=False, name=name)

    def _block_params(self, i):
        d, ff = self.d_model, self.d_ff
        v = sym.Variable
        p = {"qkv_weight": v("l%d_qkv_weight" % i, shape=(3 * d, d)),
             "out_weight": v("l%d_out_weight" % i, shape=(d, d))}
        if self.bias:
            p["qkv_bias"] = v("l%d_qkv_bias" % i, shape=(3 * d,))
            p["out_bias"] = v("l%d_out_bias" % i, shape=(d,))
        if self.num_experts:
            e = self.num_experts
            p["router_weight"] = v("l%d_router_weight" % i, shape=(d, e))
            p["gate_weight"] = v("l%d_gate_weight" % i, shape=(e, d, ff))
            p["down_weight"] = v("l%d_down_weight" % i, shape=(e, ff, d))
            p["up_weight"] = v("l%d_up_weight" % i, shape=(e, d, ff))
        else:
            p["ffn1_weight"] = v("l%d_ffn1_weight" % i, shape=(ff, d))
            p["ffn2_weight"] = v("l%d_ffn2_weight" % i, shape=(d, ff))
            if self.bias:
                p["ffn1_bias"] = v("l%d_ffn1_bias" % i, shape=(ff,))
                p["ffn2_bias"] = v("l%d_ffn2_bias" % i, shape=(d,))
        return p

    def _qkv(self, x, p, i, index=None):
        """The three projections of the normed stream, ready for the
        attention op: QK-norm over the whole projections, then rotary
        positions — each row's own `index` in a decode step, 0..T-1
        without one — so K reaches the ring already rotated."""
        qkv = self._linear(x, p, "qkv", 3 * self.d_model, "l%d_qkv" % i)
        q, k, v = sym.SliceChannel(qkv, num_outputs=3, axis=2,
                                   name="l%d_qkv_split" % i)
        if self.qk_norm:
            q = self._norm(q, "l%d_qnorm" % i)
            k = self._norm(k, "l%d_knorm" % i)
        if self.positions == "rotary":
            rope = dict(num_heads=self.num_heads, theta=self.rope_theta)
            if index is None:
                q = sym._rotary(q, name="l%d_qrope" % i, **rope)
                k = sym._rotary(k, name="l%d_krope" % i, **rope)
            else:
                q = sym._rotary_at(q, index, name="l%d_qrope" % i, **rope)
                k = sym._rotary_at(k, index, name="l%d_krope" % i, **rope)
        return q, k, v

    def _attn_out(self, ctx, p, i):
        return self._linear(ctx, p, "out", self.d_model, "l%d_proj" % i)

    def _ffn(self, h, p, i, train, loads=None):
        """The block's second half on the residual stream `h`.  A routed
        model's serving graphs pass `loads`, which collects each layer's
        tokens-per-expert output."""
        x = self._norm(h, "l%d_ln2" % i)
        if self.num_experts:
            f = sym.MoE(x, p["router_weight"], p["gate_weight"],
                        p["down_weight"], p["up_weight"],
                        num_experts=self.num_experts, hidden_size=self.d_ff,
                        k=self.experts_per_token, act_type="silu",
                        gated=True, no_bias=True, normalize=False,
                        return_load=loads is not None, name="l%d_moe" % i)
            if loads is not None:
                loads.append(f[1])
                f = f[0]
        else:
            f = sym.Activation(
                self._linear(x, p, "ffn1", self.d_ff, "l%d_ffn1" % i),
                act_type="relu", name="l%d_gelu" % i)
            f = self._linear(f, p, "ffn2", self.d_model, "l%d_ffn2" % i)
        if train and self.dropout > 0:
            f = sym.Dropout(f, p=self.dropout, name="l%d_drop" % i)
        return h + f

    def _block_train(self, h, i, train):
        p = self._block_params(i)
        x = self._norm(h, "l%d_ln1" % i)
        q, k, v = self._qkv(x, p, i)
        attn = sym._sdp_attention(q, k, v, num_heads=self.num_heads,
                                  causal=True, name="l%d_attn" % i)
        a = self._attn_out(attn[0], p, i)
        if train and self.dropout > 0:
            a = sym.Dropout(a, p=self.dropout, name="l%d_adrop" % i)
        h = h + a
        return self._ffn(h, p, i, train)

    def _embed(self, data, index=None):
        """Token embedding (plus the learned position table's rows: each
        row's own `index` in a decode step, 0..T-1 without one).
        Returns (hidden, embed_weight)."""
        embed_w = self._embed_weight()
        h = sym.Embedding(data, weight=embed_w, input_dim=self.vocab,
                          output_dim=self.d_model, name="embed")
        if self.positions == "learned":
            if index is None:
                h = sym._add_positional(h, self._pos_weight(),
                                        name="pos_add")
            else:
                h = sym._add_positional_at(h, self._pos_weight(), index,
                                           name="pos_add")
        return h, embed_w

    def _trunk(self, data, train):
        """Embedding + positions + the block stack + final norm; returns
        hidden states ``(N, T, d_model)``."""
        h, embed_w = self._embed(data)
        for i in range(self.num_layers):
            h = self._block_train(h, i, train)
        return self._norm(h, "ln_f"), embed_w

    def _head(self, h2d, embed_w, name):
        """LM head over flattened positions: ``h @ W^T`` with W the
        embedding table (the tie halves head params and is the reference
        transformer-LM convention) or the head's own matrix."""
        w = embed_w if self.tied_head else sym.Variable(
            "head_weight", shape=(self.vocab, self.d_model))
        return sym.dot(h2d, w, transpose_b=True, name=name)

    def _serving_outputs(self, logits, rings, loads, last_token, slot):
        """``[logits, rings..., last_token, token, moe_load]``: what is
        threaded from call to call (the rings, then the last sampled
        token of every slot), then what the batcher reads — the greedy
        token of each row and, for a routed model, tokens per (layer,
        expert) of this call."""
        sampled = sym._greedy_token(logits, last_token, slot, name="token")
        extra = []
        if loads:
            extra = [sym.Reshape(sym.Concat(*loads, dim=0),
                                 shape=(self.num_layers, self.num_experts),
                                 name="moe_load")]
        return sym.Group([logits] + rings + [sampled[1], sampled[0]] + extra)

    def extra_outputs(self):
        """Names of the serving graphs' outputs after the token."""
        return ("moe_load",) if self.num_experts else ()

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

    def cache_names(self):
        """The serving graphs' KV-ring input names, in wire order."""
        names = []
        for i in range(self.num_layers):
            names += ["k_cache_%d" % i, "v_cache_%d" % i]
        return names

    def cache_shape(self, slots, max_len=None):
        """THE stored shape of one layer's K ring, and of its V ring, for
        `slots` pages of `max_len` positions (default: the model's
        own): ``(slots, num_heads, max_len, d_head)``.  Whoever allocates
        or sizes a ring asks here (serving/decode.py, which adds the +1
        scratch slot, and the server's admission), and the ring ops
        (ops/attention.py) read and write exactly this order.

        One order for every head width, measured on a TPU v5e (PERF.md
        section 6, PR 26): the runtime stores a 64-wide minor axis with
        the POSITIONS on the lanes (``[slot][head][d_head][position]``,
        dense) and a 128-wide one as written, which is in both cases
        the layout the decode step's attention reads; rings transposed by
        hand were no faster at d_head 64 and 18-36% slower at 128."""
        return (int(slots), self.num_heads,
                self.max_len if max_len is None else int(max_len),
                self.d_head)

    def _cache_vars(self):
        return {n: sym.Variable(n) for n in self.cache_names()}

    def prefill_symbol(self):
        """Prefill one prompt (batch 1, padded to a sequence bucket):
        outputs ``[next_logits (1, vocab), k_cache_0', v_cache_0',
        ..., last_token', token (1,)]``.  Inputs beyond the caches:
        ``data (1, T)``, ``slot (1,)``, ``length (1,)`` (true prompt
        length), ``last_token (slots + 1,)``."""
        data = sym.Variable("data")
        slot = sym.Variable("slot")
        length = sym.Variable("length")
        last_token = sym.Variable("last_token")
        caches = self._cache_vars()
        h, embed_w = self._embed(data)
        outs, loads = [], [] if self.num_experts else None
        for i in range(self.num_layers):
            p = self._block_params(i)
            x = self._norm(h, "l%d_ln1" % i)
            q, k, v = self._qkv(x, p, i)
            attn = sym._sdp_attention(q, k, v, num_heads=self.num_heads,
                                      causal=True, name="l%d_attn" % i)
            wrote = sym._kv_cache_write(
                caches["k_cache_%d" % i], caches["v_cache_%d" % i],
                attn[1], attn[2], slot, name="l%d_kv_write" % i)
            outs += [wrote[0], wrote[1]]
            h = h + self._attn_out(attn[0], p, i)
            h = self._ffn(h, p, i, train=False, loads=loads)
        h = self._norm(h, "ln_f")
        # logits at the prompt's true tail, not the pad
        last = sym._take_step(h, length - 1, name="last_h")
        logits = self._head(last, embed_w, "next_logits")
        return self._serving_outputs(logits, outs, loads, last_token, slot)

    def decode_symbol(self):
        """One decode step for a packed session batch: inputs ``data
        (B, 1)`` (each session's last token, or a negative number for
        "the one ``last_token[slot]`` holds"), ``slot (B,)``, ``length
        (B,)`` (tokens already cached), plus the rings and ``last_token
        (slots + 1,)``; outputs ``[logits (B, vocab), k_cache_0',
        v_cache_0', ..., last_token', token (B,)]``."""
        data = sym.Variable("data")
        slot = sym.Variable("slot")
        length = sym.Variable("length")
        last_token = sym.Variable("last_token")
        caches = self._cache_vars()
        data = sym._token_feed(data, last_token, slot, name="token_feed")
        h, embed_w = self._embed(data, index=length)
        outs, loads = [], [] if self.num_experts else None
        for i in range(self.num_layers):
            p = self._block_params(i)
            x = self._norm(h, "l%d_ln1" % i)
            q, k, v = self._qkv(x, p, i, index=length)
            step = sym._cached_attention(
                q, k, v, caches["k_cache_%d" % i],
                caches["v_cache_%d" % i], slot, length,
                num_heads=self.num_heads, name="l%d_attn" % i)
            outs += [step[1], step[2]]
            h = h + self._attn_out(step[0], p, i)
            h = self._ffn(h, p, i, train=False, loads=loads)
        h = self._norm(h, "ln_f")
        flat = sym.Reshape(h, shape=(-1, self.d_model), name="flat")
        logits = self._head(flat, embed_w, "next_logits")
        return self._serving_outputs(logits, outs, loads, last_token, slot)
