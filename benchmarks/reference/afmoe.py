"""Plain reference for the `afmoe` family: Arcee's Trinity decoder
(`arcee-ai/Trinity-Mini` config.json, `model_type` `afmoe`; the layer
equations as the `afmoe` modelling code of the `transformers` library has
them) as one full forward pass in straightforward float32 `jax.numpy` at
"highest" matmul precision — no cache, no ring, no kernel, no batching.
Independent of `mxnet_tpu`: only the parameter names and layouts follow
the model under test.

With `d` the hidden size, `H` query heads and `H_kv` K/V heads of `d_h`,
`W` the window, `E` experts of which a token takes `k`:

    h0      = embed[tokens] * sqrt(d)                       (mup_enabled)
    block:  a  = h + RMS_post_attn(Attn(RMS_in(h)))
            h' = a + RMS_post_mlp(FFN(RMS_pre_mlp(a)))      a gain each
    Attn(x): q = x Wq -> (H, d_h); k = x Wk, v = x Wv -> (H_kv, d_h)
            g = x Wg -> (H * d_h)
            q = RMS_{d_h}(q) * gq;  k = RMS_{d_h}(k) * gk   one (d_h,) gain
            sliding layer only: q, k = rotary(q, k)         rotate-half
            s_ij = q_i . k_j / sqrt(d_h),  j <= i,  sliding: i - j < W
            o = softmax_j(s) v       (a K/V head serves H / H_kv query heads)
            out = (o * sigmoid(g)) Wo
    FFN, layer < num_dense_layers:  Wd(silu(x Wg') * (x Wu'))
    FFN, expert layer:  sc = sigmoid(x Wr)                  float32, (E,)
            S   = top-k of (sc + b)                         b: selection only
            w_e = route_scale * sc_e / (sum_{e' in S} sc_e' + 1e-20)
            y   = Shared(x) + sum_{e in S} w_e Expert_e(x)
    logits  = RMS_final(h_L) W_head                         untied

**One chip's share.**  `held` ``(first, count)`` says which experts'
matrices `params` holds (``gate/up/down_weight`` then have `count` rows):
the choice S and the weights `w_e` stay over all E, and the terms of
experts outside the range are left out — what the other chips of the
layer would add is neither computed nor stood in for.  A sliced
vocabulary is simply a smaller one: `embed_weight` and `head_weight` have
the slice's rows.  `held` None with all E experts is the uncut model.

Departures from the published code: Q, K, V and the gate are ONE fused
projection ``[q | k | v | g]``; expert matrices are stacked ``(E, d,
ff)`` / ``(E, ff, d)`` and each expert is applied to every position with
its weight (0 where it was not chosen) instead of gathering tokens — the
same sums in another order; the dense FFN's and the shared expert's gate
and up are as the model under test holds them (``[a | b]`` fused for the
dense layer, separate ``(d, s)`` matrices for the shared expert);
attention is computed in blocks of query positions so that two thousand
positions at the published widths fit beside a serving tenant; no
dropout, no auxiliary loss, no load-balancing update of `b` (training
only).
"""
import functools

import jax
import jax.numpy as jnp

ATTENTION = ("ln1_gamma", "qkv_weight", "qnorm_gamma", "knorm_gamma",
             "out_weight", "ln1_post_gamma")
DENSE = ("ln2_gamma", "ffn1_weight", "ffn2_weight", "ln2_post_gamma")
ROUTED = ("ln2_gamma", "router_weight", "router_bias", "gate_weight",
          "up_weight", "down_weight", "shared_gate_weight",
          "shared_up_weight", "shared_down_weight", "ln2_post_gamma")
QUERY_BLOCK = 256


def _rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def _rotary(x, theta):
    """x (heads, T, d_head): rotate-half over the whole head, row t at
    position t."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "d_head",
                                             "window", "eps", "theta"))
def attention_block(x, ln1_gamma, qkv_weight, qnorm_gamma, knorm_gamma,
                    out_weight, ln1_post_gamma, heads, kv_heads, d_head,
                    window, eps, theta):
    """``x + RMS_post(Attn(RMS_in(x)))``; `window` 0 is a full layer (no
    position signal), `window` W a sliding one (rotary)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        qw, kw = heads * d_head, kv_heads * d_head
        proj = _rms(x, ln1_gamma, eps) @ qkv_weight.T
        q, k, v, g = jnp.split(proj, (qw, qw + kw, qw + 2 * kw), axis=-1)
        q = _rms(q.reshape(t, heads, d_head), qnorm_gamma, eps)
        k = _rms(k.reshape(t, kv_heads, d_head), knorm_gamma, eps)
        q, k = q.transpose(1, 0, 2), k.transpose(1, 0, 2)
        v = v.reshape(t, kv_heads, d_head).transpose(1, 0, 2)
        if window:
            q, k = _rotary(q, theta), _rotary(k, theta)
        # each K/V head repeated for its group of query heads
        k, v = (jnp.repeat(part, heads // kv_heads, axis=0) for part in (k, v))
        out = []
        for start in range(0, t, QUERY_BLOCK):   # blocks of query positions
            i = jnp.arange(start, min(start + QUERY_BLOCK, t))[:, None]
            j = jnp.arange(t)[None, :]
            keep = j <= i
            if window:
                keep &= i - j < window
            s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + QUERY_BLOCK],
                           k) / jnp.sqrt(float(d_head))
            s = jnp.where(keep, s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1),
                                  v))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, qw)
        attn = (o * jax.nn.sigmoid(g)) @ out_weight.T
        return x + _rms(attn, ln1_post_gamma, eps)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_block(x, ln2_gamma, ffn1_weight, ffn2_weight, ln2_post_gamma, eps):
    with jax.default_matmul_precision("highest"):
        gate, up = jnp.split(ffn1_weight, 2, axis=0)
        y = _swiglu(_rms(x, ln2_gamma, eps), gate.T, up.T, ffn2_weight.T)
        return x + _rms(y, ln2_post_gamma, eps)


def route(x, router_weight, router_bias, top_k, route_scale, route_norm):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,): how far the last chosen expert's selection score lies
    above the first one left out)."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router_weight)
    select = scores + router_bias
    ranked = jnp.argsort(-select, axis=-1)
    best = jnp.take_along_axis(select, ranked[:, :top_k + 1], axis=-1)
    chosen = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], ranked[:, :top_k]].set(1.0)
    weights = scores * chosen
    if route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * route_scale, best[:, top_k - 1] - best[:, top_k]


def expert_layer(x, router_weight, router_bias, gate_weight, up_weight,
                 down_weight, shared, top_k, route_scale, route_norm, first):
    """The expert layer's output for normed input `x (T, d)`: the routed
    sum over the experts whose matrices are given — experts `first` ..
    `first + count` of the router's E — plus, where `shared` ``(gate, up,
    down)`` is not None, the shared expert.  Returns (y, margin)."""
    weights, margin = route(x, router_weight, router_bias, top_k,
                            route_scale, route_norm)
    count = gate_weight.shape[0]
    mine = weights[:, first:first + count]

    def one(y, expert):       # every position through one expert, weighted
        gate, up, down, w = expert
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (gate_weight, up_weight, down_weight, mine.T))
    if shared is not None:
        y = y + _swiglu(x, *shared)
    return y, margin


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "route_norm", "first", "eps"))
def routed_block(x, ln2_gamma, router_weight, router_bias, gate_weight,
                 up_weight, down_weight, shared_gate_weight,
                 shared_up_weight, shared_down_weight, ln2_post_gamma,
                 top_k, route_scale, route_norm, first, eps):
    with jax.default_matmul_precision("highest"):
        y, margin = expert_layer(
            _rms(x, ln2_gamma, eps), router_weight, router_bias, gate_weight,
            up_weight, down_weight,
            (shared_gate_weight, shared_up_weight, shared_down_weight),
            top_k, route_scale, route_norm, first)
        return x + _rms(y, ln2_post_gamma, eps), margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def forward(params, config, tokens, rows=None):
    """One sequence: (logits at the positions `rows` (default all) over
    the vocabulary `params` holds, margins (expert layers, T))."""
    d = config["hidden_size"]
    x = params["embed_weight"][jnp.asarray(tokens, jnp.int32)]
    if config["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(d))
    eps = float(config["rms_norm_eps"])
    first = config.get("held_experts", (0, None))[0]
    margins = []
    for i, kind in enumerate(config["layer_types"]):
        layer = lambda names: [params["l%d_%s" % (i, n)]  # noqa: E731
                               for n in names]
        x = attention_block(
            x, *layer(ATTENTION), heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            d_head=config["head_dim"],
            window=(config["sliding_window"]
                    if kind == "sliding_attention" else 0),
            eps=eps, theta=float(config["rope_theta"]))
        if i < config["num_dense_layers"]:
            x = dense_block(x, *layer(DENSE), eps=eps)
        else:
            x, margin = routed_block(
                x, *layer(ROUTED), top_k=config["num_experts_per_tok"],
                route_scale=float(config["route_scale"]),
                route_norm=bool(config["route_norm"]), first=first, eps=eps)
            margins.append(margin)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return (_head(x, params["ln_f_gamma"], params["head_weight"], eps),
            jnp.stack(margins))


def logits(params, config, tokens):
    return forward(params, config, tokens)[0]
