"""Plain reference for the `mistral4` family: the Mistral-Small-4 decoder
(`mistralai/Mistral-Small-4-119B-2603` config.json, `model_type`
`mistral4`, 119B-A6.5B; the block descends from DeepSeek-V3's: multi-head
latent attention and softmax-routed experts beside a shared one) as one
full forward pass in straightforward float32 `jax.numpy` at "highest"
matmul precision — no cache, no ring, no kernel, no absorbed form, no
batching: every head's K and V are made from the latent rows and
attention is a causal softmax.  Independent of `mxnet_tpu`: only the
parameter names follow the model under test.

With `d` the hidden size, eps `rms_norm_eps`, H heads, `x = RMS_in(h)`
and no bias anywhere:

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g              a PLAIN gain
    c_q = RMS_qa(x W_qa)                                    q_lora_rank
    q   = c_q W_qb        -> H heads of [q_nope (n) | q_rope (r)]
    [c_kv (kv_lora_rank) | k_r (r)] = x W_kva;  c = RMS_kva(c_kv)
    [k_nope_h (n) | v_h (v)] = c W_kvb,h                    per head h
    k_r is ONE rotary key shared by all H heads
    rotary on q_rope_h and k_r only, pairs (2j, 2j+1)  (rope_interleave),
        angle p * f_j, YaRN's frequencies over the r/2 pairs:
        b_j = theta^(-2j/r)
        f_j = (1 - m_j) * b_j / factor + m_j * b_j
        m_j = 1 - clip((j - lo) / (hi - lo), 0, 1)
        lo = floor(t(beta_fast)), hi = ceil(t(beta_slow)), in [0, r - 1]
        t(u) = r * ln(original_max / (2 pi u)) / (2 ln theta)
        cos, sin times (0.1 * mscale * ln factor + 1)
                     / (0.1 * mscale_all_dim * ln factor + 1)
    q at position p times 1 + beta * ln(1 + floor(p / original_max))
        (`llama_4_scaling_beta`; exactly 1 below original_max)
    score_h[t, s] = sigma * (q_nope_h[t] . k_nope_h[s]
                             + q_rope_h[t] . k_r[s]),  s <= t
    ctx_h = softmax_s(score_h) v_h;  out = concat_h(ctx_h) W_o
    a  = h + out
    h' = a + MoE(RMS_post(a))
    logits = RMS_f(h_L) W_head                               untied

Experts (E routed, k a token, of width f, and one shared of width s):

    p = score(x W_r)      float32, softmax over all E (or sigmoid)
    S = the k largest;  w_e = p_e / sum_{e' in S} p_e' * routed_scaling
    MoE(x) = sum_{e in S} w_e Expert_e(x) + Shared(x)       no gate on it
    Expert(x) = (silu(x G) * (x U)) D

**Three readings the config leaves open** are read from the
configuration's `assumed`, not fixed here: `sigma`
(``assumed.softmax_scale.value``), the router's score function
(``assumed.router.scoring_func``, no selection bias), and the query's
position scale (``assumed.query_scale``: the form above with `beta` =
``rope_parameters.llama_4_scaling_beta``).

**The checkpoint's layout.**  `params` hold `l<i>_qb_weight` and
`l<i>_kva_weight` as the published checkpoint lays them out: `W_qb`'s rows
head by head, ``[q_nope_h | q_rope_h]``, and the rotary rows of both with
the pairs INTERLEAVED, ``(2j, 2j + 1)``.  The model under test stores them
by kind and de-interleaved (`families/mistral4.py checkpoint_layout` maps
its parameters to these); a test ties the two.

**One chip's share.**  `held` ``(first, count)`` — by default the
configuration's `held_experts` — says which experts' matrices `params`
holds: the choice S and the weights stay over all E, and the terms of
experts outside the range are left out; the shared expert is computed
whole.  A sliced vocabulary is a smaller one.

Departures from the published code, none in the mathematics: expert
matrices are stacked ``(E, d, f)`` / ``(E, f, d)`` and each held expert is
applied to every position with its weight (0 where it was not chosen);
attention is computed in blocks of query positions so that two and a half
thousand positions fit beside a serving tenant.  Not run: the vision
tower, the router's auxiliary loss, dropout.
"""
import functools
import math

import jax
import jax.numpy as jnp

ATTENTION = ("ln1_gamma", "qa_weight", "qa_norm_gamma", "qb_weight",
             "kva_weight", "kva_norm_gamma", "kvb_weight", "out_weight")
ROUTED = ("ln2_gamma", "router_weight", "gate_weight", "up_weight",
          "down_weight", "shared_gate_weight", "shared_up_weight",
          "shared_down_weight")
QUERY_BLOCK = 256


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def yarn_frequencies(rope, pairs_of):
    """(f_j over the ``pairs_of / 2`` pairs, the factor on cos and sin) of
    `rope`, the configuration's `rope_parameters`, for a rotary part of
    `pairs_of` channels; (lo, hi) ride as the third entry for the tests."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def turns_at(u):
        return pairs_of * math.log(original / (2 * math.pi * u)) / (
            2 * math.log(theta))

    lo = max(math.floor(turns_at(rope["beta_fast"])), 0)
    hi = min(math.ceil(turns_at(rope["beta_slow"])), pairs_of - 1)
    j = jnp.arange(pairs_of // 2, dtype=jnp.float32)
    base = theta ** (-2.0 * j / pairs_of)
    keep = 1.0 - jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    grow = 0.1 * math.log(factor)
    attention_factor = ((grow * rope["mscale"] + 1.0)
                        / (grow * rope["mscale_all_dim"] + 1.0))
    return ((1.0 - keep) * base / factor + keep * base, attention_factor,
            (lo, hi))


def _rotary(x, freqs, attention_factor):
    """``x (..., T, r)`` with the pairs interleaved, row t at position t."""
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = (jnp.cos(ang) * attention_factor,
                jnp.sin(ang) * attention_factor)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def query_factor(positions, beta, period):
    """``1 + beta * ln(1 + floor(p / period))``."""
    return 1.0 + beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / period))


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "value", "sigma", "eps", "yarn", "query_scale"))
def attention_mixer(x, ln1_gamma, qa_weight, qa_norm_gamma, qb_weight,
                    kva_weight, kva_norm_gamma, kvb_weight, out_weight,
                    heads, nope, rope, value, sigma, eps, yarn, query_scale):
    """``x + MLA(RMS_in(x))`` for ``x (T, d)``; `yarn` the
    `rope_parameters` as sorted items, `query_scale` ``(beta, period)`` or
    None."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        freqs, factor, _ = yarn_frequencies(dict(yarn), rope)
        normed = _rms(x, ln1_gamma, eps)
        c_q = _rms(normed @ qa_weight.T, qa_norm_gamma, eps)
        q = (c_q @ qb_weight.T).reshape(t, heads, nope + rope)
        q = q.transpose(1, 0, 2)                          # (H, T, n + r)
        kva = normed @ kva_weight.T
        rank = kva.shape[-1] - rope
        c = _rms(kva[:, :rank], kva_norm_gamma, eps)
        k_r = _rotary(kva[:, rank:], freqs, factor)       # (T, r): ONE key
        kv = (c @ kvb_weight.T).reshape(t, heads, nope + value)
        kv = kv.transpose(1, 0, 2)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], freqs, factor)
        if query_scale is not None:
            grow = query_factor(jnp.arange(t), *query_scale)[None, :, None]
            grow = grow.astype(x.dtype)
            q_nope, q_rope = q_nope * grow, q_rope * grow
        out = []
        for start in range(0, t, QUERY_BLOCK):   # blocks of query positions
            rows = slice(start, start + QUERY_BLOCK)
            i = jnp.arange(start, min(start + QUERY_BLOCK, t))[:, None]
            s = sigma * (jnp.einsum("hqd,hkd->hqk", q_nope[:, rows], k_nope)
                         + jnp.einsum("hqd,kd->hqk", q_rope[:, rows], k_r))
            s = jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1),
                                  v))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2)
        return x + o.reshape(t, heads * value) @ out_weight.T


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router_weight, top_k, norm_topk, scale, scoring, first=0,
          count=None):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,): how far the choice among the experts `first` .. `first +
    count` (default all) lies from changing — the least distance of one of
    THEIR scores from the edge of the choice, the first score left out for
    an expert that is chosen and the last chosen for one that is not, as a
    share of the last chosen score.  A tie between two experts outside the
    range moves no term of the range's sum, only the weights' common
    divisor, and by less than the tie is wide.)"""
    logits = x.astype(jnp.float32) @ router_weight
    probs = (jax.nn.softmax(logits, axis=-1) if scoring == "softmax"
             else jax.nn.sigmoid(logits))
    ranked = jnp.argsort(-probs, axis=-1)
    best = jnp.take_along_axis(probs, ranked[:, :top_k + 1], axis=-1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ranked[:, :top_k]].set(1.0)
    weights = probs * chosen
    if norm_topk:
        weights = weights / weights.sum(-1, keepdims=True)
    from_edge = jnp.where(chosen > 0, probs - first_out, last_in - probs)
    mine = slice(first, None if count is None else first + count)
    return (weights * scale,
            (from_edge[:, mine] / last_in).min(axis=-1))


def expert_layer(x, router_weight, gate_weight, up_weight, down_weight,
                 shared, top_k, norm_topk, scale, scoring, first,
                 shared_times=1.0):
    """The expert layer's output for normed input `x (T, d)`: the routed
    sum over the experts whose matrices are given — experts `first` ..
    `first + count` of the router's E — plus `shared_times` (1: once) the
    shared expert ``(gate, up, down)``.  Returns (y, margin)."""
    count = gate_weight.shape[0]
    weights, margin = route(x, router_weight, top_k, norm_topk, scale,
                            scoring, first, count)
    # (the weights are float32; the sum runs in the dtype of `x`)
    mine = weights[:, first:first + count].astype(x.dtype)

    def one(y, expert):       # every position through one expert, weighted
        gate, up, down, w = expert
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (gate_weight, up_weight, down_weight, mine.T))
    return y + shared_times * _swiglu(x, *shared), margin


@functools.partial(jax.jit, static_argnames=(
    "top_k", "norm_topk", "scale", "scoring", "first", "eps"))
def routed_block(x, ln2_gamma, router_weight, gate_weight, up_weight,
                 down_weight, shared_gate_weight, shared_up_weight,
                 shared_down_weight, top_k, norm_topk, scale, scoring, first,
                 eps):
    """``x + MoE(RMS_post(x))``."""
    with jax.default_matmul_precision("highest"):
        y, margin = expert_layer(
            _rms(x, ln2_gamma, eps), router_weight, gate_weight, up_weight,
            down_weight,
            (shared_gate_weight, shared_up_weight, shared_down_weight),
            top_k, norm_topk, scale, scoring, first)
        return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def forward(params, config, tokens, rows=None, dtype=None, held=None):
    """One sequence: (logits at the positions `rows` (default all) over
    the vocabulary `params` holds, margins (layers, T)).  `held` ``(first,
    count)``: the experts `params` holds (default the configuration's
    `held_experts`).  `dtype`: THE CONTROL — every weight cast to it as it
    is used, so that activations are of it too (the router's product stays
    float32 of the cast operands) — which the family's check has to
    refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    x = cast(params["embed_weight"][jnp.asarray(tokens, jnp.int32)])
    eps = float(config["rms_norm_eps"])
    first = (held or config.get("held_experts") or (0, None))[0]
    rope = config["rope_parameters"]
    assumed = config["assumed"]
    query_scale = None
    if assumed["query_scale"]["applied"]:
        query_scale = (float(rope["llama_4_scaling_beta"]),
                       float(rope["original_max_position_embeddings"]))
    yarn = tuple(sorted((k, v) for k, v in rope.items()
                        if not isinstance(v, str)))
    margins = []
    for i in range(config["num_hidden_layers"]):
        layer = lambda names: [cast(params["l%d_%s" % (i, n)])  # noqa: E731
                               for n in names]
        x = attention_mixer(
            x, *layer(ATTENTION), heads=config["num_attention_heads"],
            nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
            value=config["v_head_dim"],
            sigma=float(assumed["softmax_scale"]["value"]), eps=eps,
            yarn=yarn, query_scale=query_scale)
        x, margin = routed_block(
            x, *layer(ROUTED), top_k=config["num_experts_per_tok"],
            norm_topk=bool(config["norm_topk_prob"]),
            scale=float(config["routed_scaling_factor"]),
            scoring=assumed["router"]["scoring_func"], first=first, eps=eps)
        margins.append(margin)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return (_head(x, cast(params["ln_f_gamma"]), cast(params["head_weight"]),
                  eps),
            jnp.stack(margins))


def logits(params, config, tokens, held=None):
    return forward(params, config, tokens, held=held)[0]
