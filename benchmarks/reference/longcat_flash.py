"""Plain reference for the `longcat_flash` family: the language model of
LongCat-Flash-Omni (`meituan-longcat/LongCat-Flash-Omni` config.json,
560B-A27B; the block is LongCat-Flash's, arXiv:2509.01322: a
shortcut-connected expert layer across two latent-attention sublayers and
two dense FFNs, zero-compute experts in the router) as one full forward
pass in straightforward float32 `jax.numpy` at "highest" matmul precision
— no cache, no ring, no kernel, no absorbed form, no batching, no sort:
every head's K and V are made from the latent rows, attention is a causal
softmax, and every held expert is applied to every position.  Independent
of `mxnet_tpu`: only the parameter names follow the model under test,
whose layers ``2l`` and ``2l + 1`` are published layer l's two sublayers.

With `d` the hidden size, `RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g` a
plain gain, no bias anywhere, for published layer l:

    a1 = h  + MLA[l,0](RMS_in0(h))          l<2l>_ln1, l<2l>_{qa,qb,kva,kvb,out}
    x1 = RMS_post0(a1)                      l<2l>_ln2
    s  = MoE[l](x1)                         l<2l>_{router,gate,up,down}
    b1 = a1 + FFN[l,0](x1)                  l<2l>_ffn{1,2}: the SAME x1
    a2 = b1 + MLA[l,1](RMS_in1(b1))         l<2l+1>_ln1, ...
    h' = a2 + FFN[l,1](RMS_post1(a2)) + s   l<2l+1>_ln2, l<2l+1>_ffn{1,2}

    FFN(x) = (silu(a) * b) W_2,  [a | b] = x W_1          width ffn_hidden_size

Latent attention (per sublayer; H heads, n = qk_nope_head_dim, r =
qk_rope_head_dim, v = v_head_dim):

    c_q = alpha_q * RMS_qa(x W_qa),  alpha_q = sqrt(d / q_lora_rank)
    q   = c_q W_qb             -> H heads of [q_nope (n) | q_rope (r)]
    [c_kv | k_r (r)] = x W_kva;   c = alpha_kv * RMS_kva(c_kv),
                                  alpha_kv = sqrt(d / kv_lora_rank)
    [k_nope_h (n) | v_h (v)] = c W_kvb,h              per head h
    rotary, base rope_theta, on q_rope_h and the ONE k_r, pairs (2j, 2j+1)
    score_h[t, s] = (n + r)^-1/2 (q_nope_h[t] . k_nope_h[s]
                                  + q_rope_h[t] . k_r[s]),   s <= t
    ctx_h = softmax_s(score_h) v_h;   out = concat_h(ctx_h) W_o

(`mla_scale_q_lora` / `mla_scale_kv_lora` multiply after the up-projection
in the published code; the projections are linear and bias-free, so the
factor on the normed latent is the same numbers.)

Experts (E real experts and Z zero-compute ones in ONE router of E + Z
columns, k a token):

    p = softmax(x1 W_r)  over E + Z, float32
    S = the k largest of p + b          b: the selection bias, choice only
    w_e = routed_scaling_factor * p_e   for e in S: NOT renormalised
    MoE(x1) = sum_{e in S, e < E} w_e (silu(x1 G_e) * (x1 U_e)) D_e
            + (sum_{e in S, e >= E} w_e) * x1           identity experts

**The checkpoint's layout.**  `params` hold `l<i>_qb_weight` and
`l<i>_kva_weight` as the published checkpoint lays them out: `W_qb`'s rows
head by head, ``[q_nope_h | q_rope_h]``, and the rotary rows of both with
the pairs INTERLEAVED.  The model under test stores them by kind and
de-interleaved (`families/longcat_flash.py checkpoint_layout`).

**One chip's share.**  The HEADS `params` holds are what its `W_qb`,
`W_kvb` and `W_o` have rows and columns for: a share's `out` is its heads'
part of the sum over heads, and the shares' parts add up to the whole
sublayer's.  `held` ``(first, count)`` — by default the configuration's
`held_experts` — says which REAL experts' matrices `params` holds: the
choice and the weights stay over all E + Z columns, the terms of real
experts outside the range are left out, and the identity term — computed
where a token lives, by every chip alike — is whole.  A sliced vocabulary
is a smaller one.

Departures from the published code, none in the mathematics: expert
matrices are stacked ``(E, d, f)`` / ``(E, f, d)`` and each held expert is
applied to every position with its weight (0 where it was not chosen); a
dense FFN's two in-projections are one fused ``[a | b]`` and its width
goes in eight blocks; attention is computed in blocks of query positions.  Not run: the audio and vision
encoders, the codec decoder, auxiliary losses, the bias's update.

`fault`, one of `FAULTS`: what a wrong program would compute, for the tests
and the family's controls.
"""
import functools

import jax
import jax.numpy as jnp

ATTENTION = ("ln1_gamma", "qa_weight", "qa_norm_gamma", "qb_weight",
             "kva_weight", "kva_norm_gamma", "kvb_weight", "out_weight")
DENSE = ("ffn1_weight", "ffn2_weight")
ROUTED = ("router_weight", "router_bias", "gate_weight", "up_weight",
          "down_weight")
FAULTS = ("no_identity", "renormalised", "no_route_scale", "no_select_bias",
          "bias_in_weights", "early_join", "no_q_rescale", "no_kv_rescale",
          "own_ffn_norm")
QUERY_BLOCK = 256
FFN_BLOCKS = 8


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def _rotary(x, theta):
    """``x (..., T, r)`` with the pairs interleaved, row t at position t."""
    r = x.shape[-1]
    freqs = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    "nope", "rope", "value", "theta", "eps", "q_scale", "kv_scale"))
def attention(x, ln1_gamma, qa_weight, qa_norm_gamma, qb_weight, kva_weight,
              kva_norm_gamma, kvb_weight, out_weight, nope, rope, value,
              theta, eps, q_scale, kv_scale):
    """``MLA(RMS_in(x))`` for ``x (T, d)``, of the heads the matrices hold:
    what the sublayer adds to the stream."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        heads = qb_weight.shape[0] // (nope + rope)
        normed = _rms(x, ln1_gamma, eps)
        c_q = q_scale * _rms(normed @ qa_weight.T, qa_norm_gamma, eps)
        q = (c_q @ qb_weight.T).reshape(t, heads, nope + rope)
        q = q.transpose(1, 0, 2)                          # (H, T, n + r)
        kva = normed @ kva_weight.T
        rank = kva.shape[-1] - rope
        c = kv_scale * _rms(kva[:, :rank], kva_norm_gamma, eps)
        k_r = _rotary(kva[:, rank:], theta)               # (T, r): ONE key
        kv = (c @ kvb_weight.T).reshape(t, heads, nope + value)
        kv = kv.transpose(1, 0, 2)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], theta)
        sigma = (nope + rope) ** -0.5
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
        return o.reshape(t, heads * value) @ out_weight.T


@jax.jit
def _ffn_block(x, a_rows, b_rows, out_cols):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ a_rows.T) * (x @ b_rows.T)) @ out_cols.T


def dense_ffn(x, gamma, ffn1_weight, ffn2_weight, eps):
    """``FFN(RMS(x; gamma))``; a gain of None: `x` is normed already.  The
    width goes in FFN_BLOCKS equal blocks, one call AFTER the other — at
    "highest" a TPU multiplies a float32 matrix as three bfloat16 ones, and
    a whole ``(24576, 6144)`` so split is 0.9 GB beside a serving tenant
    that has 0.25 to spare —: the same sum, a block's columns at a time."""
    if gamma is not None:
        x = _normed(x, gamma, eps)
    f = ffn2_weight.shape[1]
    step = f // FFN_BLOCKS if f % FFN_BLOCKS == 0 else f
    y = 0.0
    for at in range(0, f, step):
        # (waited for: dispatched ahead, every block's slices and splits
        # would stand on the device at once — 16.8 GB in use was read)
        y = jax.block_until_ready(
            y + _ffn_block(x, ffn1_weight[at:at + step],
                           ffn1_weight[f + at:f + at + step],
                           ffn2_weight[:, at:at + step]))
    return y


def route(x, router_weight, router_bias, top_k, scale, zero, first=0,
          count=None, fault=None):
    """(weights (T, E + Z) — `w_e` for the chosen columns, 0 elsewhere —,
    margins (2, T): how far the choice lies from changing in a way a chip
    that holds the real experts `first` .. `first + count` (default all)
    FEELS, each as a share of the last chosen probability —

    0. the HELD experts': the least distance of one of their biased
       scores from the edge of the choice (the first left out for a
       chosen one, the last chosen for one that is not);
    1. the ZERO-COMPUTE experts': the least distance of a chosen one from
       the first column left out that is NOT zero-compute, and of one left
       out from the last chosen column that is not — two zero-compute
       experts changing places move the identity term by the difference
       of two probabilities that tie.

    A tie between two real experts of other chips changes no term
    computed here.)"""
    probs = jax.nn.softmax(x.astype(jnp.float32)
                           @ router_weight.astype(jnp.float32), axis=-1)
    real = probs.shape[-1] - zero
    bias = router_bias.astype(jnp.float32)
    biased = probs if fault == "no_select_bias" else probs + bias
    ranked = jnp.argsort(-biased, axis=-1)
    at = jnp.arange(x.shape[0])[:, None]
    chosen = jnp.zeros(probs.shape, bool).at[at, ranked[:, :top_k]].set(True)
    weights = jnp.where(chosen, biased if fault == "bias_in_weights"
                        else probs, 0.0)
    if fault == "renormalised":
        weights = weights / weights.sum(-1, keepdims=True)
    if fault != "no_route_scale":
        weights = weights * scale
    best = jnp.take_along_axis(biased, ranked[:, :top_k + 1], axis=-1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    last_prob = jnp.take_along_axis(probs, ranked[:, top_k - 1:top_k], -1)
    from_edge = jnp.where(chosen, biased - first_out, last_in - biased)
    mine = slice(first, real if count is None else first + count)
    is_zero = jnp.arange(probs.shape[-1]) >= real
    inf = jnp.float32(jnp.inf)
    out_real = jnp.where(~chosen & ~is_zero, biased, -inf).max(
        -1, keepdims=True)
    in_real = jnp.where(chosen & ~is_zero, biased, inf).min(-1, keepdims=True)
    zero_edge = jnp.where(chosen, biased - out_real, in_real - biased)
    margins = jnp.stack([
        (from_edge[:, mine] / last_prob).min(axis=-1, initial=inf),
        (jnp.where(is_zero, zero_edge, inf) / last_prob).min(axis=-1)])
    return weights, margins


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "zero", "first", "fault"))
def expert_layer(x, router_weight, router_bias, gate_weight, up_weight,
                 down_weight, top_k, scale, zero, first, fault=None,
                 identity_times=1.0):
    """``MoE(x)`` for normed input `x (T, d)`: the routed sum over the real
    experts whose matrices are given — experts `first` .. `first + count`
    of the router's E — plus `identity_times` (1: once) the identity
    term.  Returns (y, margins (2, T))."""
    with jax.default_matmul_precision("highest"):
        count = gate_weight.shape[0]
        weights, margins = route(x, router_weight, router_bias, top_k, scale,
                                 zero, first, count, fault)
        # (the weights are float32; the sums run in the dtype of `x`)
        mine = weights[:, first:first + count].astype(x.dtype)

        def one(y, expert):   # every position through one expert, weighted
            gate, up, down, w = expert
            return y + w[:, None] * (
                (jax.nn.silu(x @ gate) * (x @ up)) @ down), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (gate_weight, up_weight, down_weight, mine.T))
        passed = weights[:, weights.shape[-1] - zero:].sum(
            -1, keepdims=True).astype(x.dtype) * x
        if fault == "no_identity":
            identity_times = 0.0
        return y + identity_times * passed, margins


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, gamma, eps):
    return _rms(x, gamma, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def geometry(config, fault=None):
    """The attention's static arguments from the configuration."""
    d = config["hidden_size"]
    scaled = {"q": config["mla_scale_q_lora"] and fault != "no_q_rescale",
              "kv": config["mla_scale_kv_lora"] and fault != "no_kv_rescale"}
    return dict(
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        value=config["v_head_dim"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        q_scale=(d / config["q_lora_rank"]) ** 0.5 if scaled["q"] else 1.0,
        kv_scale=(d / config["kv_lora_rank"]) ** 0.5 if scaled["kv"] else 1.0)


def layer(h, params, config, l, cast=lambda w: w, held=None, fault=None):
    """Published layer l on the stream ``h (T, d)``: (h', margins (2, T))."""
    eps = float(config["rms_norm_eps"])
    first = (held or config.get("held_experts") or (0, None))[0]

    def of(i, names):
        return [cast(params["l%d_%s" % (i, n)]) for n in names]

    geo = geometry(config, fault)
    a1 = h + attention(h, *of(2 * l, ATTENTION), **geo)
    x1 = _normed(a1, cast(params["l%d_ln2_gamma" % (2 * l)]), eps)
    s, margins = expert_layer(
        x1, *of(2 * l, ROUTED), top_k=config["moe_topk"],
        scale=float(config["routed_scaling_factor"]),
        zero=config["zero_expert_num"], first=first, fault=fault)
    if fault == "own_ffn_norm":   # (a norm of its own: another's gain)
        f0 = dense_ffn(a1, cast(params["l%d_ln2_gamma" % (2 * l + 1)]),
                       *of(2 * l, DENSE), eps=eps)
    else:
        f0 = dense_ffn(x1, None, *of(2 * l, DENSE), eps=eps)
    b1 = a1 + f0
    if fault == "early_join":     # one sublayer early: before MLA[l,1]
        b1 = b1 + s
    a2 = b1 + attention(b1, *of(2 * l + 1, ATTENTION), **geo)
    f1 = dense_ffn(a2, cast(params["l%d_ln2_gamma" % (2 * l + 1)]),
                   *of(2 * l + 1, DENSE), eps=eps)
    out = a2 + f1
    return (out if fault == "early_join" else out + s), margins


def forward(params, config, tokens, rows=None, dtype=None, held=None,
            fault=None):
    """One sequence: (logits at the positions `rows` (default all) over
    the vocabulary `params` holds, margins (layers, 2, T) — `route`'s).
    `held` ``(first, count)``: the real experts `params` holds (default
    the configuration's `held_experts`).  `dtype`: THE CONTROL — every
    weight cast to it as it is used, so that activations are of it too
    (the router's product stays float32 of the cast operands) — which the
    family's check has to refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    h = cast(params["embed_weight"][jnp.asarray(tokens, jnp.int32)])
    margins = []
    for l in range(config["num_layers"]):
        h, margin = layer(h, params, config, l, cast, held, fault)
        margins.append(margin)
    if rows is not None:
        h = h[jnp.asarray(rows, jnp.int32)]
    return (_head(h, cast(params["ln_f_gamma"]), cast(params["head_weight"]),
                  float(config["rms_norm_eps"])),
            jnp.stack(margins))


def logits(params, config, tokens, held=None):
    return forward(params, config, tokens, held=held)[0]
