"""Plain reference for the `qwen3_next` family: the Qwen3-Next decoder
(`Qwen/Qwen3-Next-80B-A3B-Instruct` config.json, `model_type`
`qwen3_next`; the layer equations as the `qwen3_next` modelling code of
the `transformers` library has them) as one full forward pass in
straightforward float32 `jax.numpy` at "highest" matmul precision — no
cache, no ring, no chunks, no kernel, no batching: the delta rule is a
`lax.scan` over POSITIONS, attention a causal softmax.  Independent of
`mxnet_tpu`: only the parameter names and layouts follow the model under
test.

With `d` the hidden size and eps `rms_norm_eps`:

    RMS(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)        float32
    block:   a  = h + Mixer(RMS_in(h))
             h' = a + MoE(RMS_post(a))
    layer i is full attention where (i + 1) % 4 == 0, else linear
    logits = RMS_f(h_L) W_head                               untied

Gated attention (H query heads of d_h over H_kv K/V heads, R = d_h *
partial_rotary_factor channels turned):

    [q | gate] = x W_q;  k = x W_k;  v = x W_v
    q = RMS_{d_h}(q; w_q);  k = RMS_{d_h}(k; w_k)      one (d_h,) gain
    channels 0..R-1 of each head of q and k: rotate-half within them
        (pairs j, j + R/2; angle pos * theta^(-2j/R)); R..d_h-1 pass
    s_ij = q_i . k_j / sqrt(d_h), j <= i;  query head n reads K/V head
        n // (H / H_kv)
    out = (softmax_j(s) v * sigmoid(gate)) W_o

Gated DeltaNet (H_k heads of q and k of d_k under H_v value heads of d_v):

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    [q | k | v] = silu(causal depthwise conv1d_4([q | k | v]))   no bias
    q = q * rsqrt(sum q^2 + 1e-6), k likewise, each of the H_k heads;
    value head n takes q/k head n // (H_v / H_k);  q = q / sqrt(d_k)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
    per value head, S (d_k, d_v) from zero:
        S = exp(g_t) S;  u = (v_t - S^T k_t) * beta_t
        S = S + k_t u^T;  o_t = S^T q_t
    y = o_t * rsqrt(mean(o_t^2) + eps) * gamma * silu(z_t)   a PLAIN gain
    out = y W_out

Experts (E routed, k a token, all of width f, and one shared of width s):

    p = softmax(x W_r)                              float32, over all E
    S = the k largest;  w_e = p_e / sum_{e' in S} p_e'     norm_topk_prob
    MoE(x) = sum_{e in S} w_e Expert_e(x) + sigmoid(x w_s) * Shared(x)
    Expert(x) = (silu(x G) * (x U)) D

**Gains as stored.**  The published checkpoint stores every `(1 + w)`
gain as `w`; the model under test stores `g = 1 + w` (its RMSNorm
multiplies by what it is given).  `params` hold the model under test's
`g`; this reference recovers `w = g - 1` and applies `1 + w` as the
equations say (`_w`), so that a program that applied its stored gain as
`w`, or the delta rule's plain `gamma` as `1 + gamma`, differs from it.

**One chip's share.**  `held_experts` ``(first, count)`` of the
configuration says which experts' matrices `params` holds: the choice S
and the weights stay over all E, and the terms of experts outside the
range are left out — what the other chips of the layer would add is
neither computed nor stood in for; the shared expert is computed whole.
A sliced vocabulary is a smaller one.

Departures from the published code, none in the mathematics: the fused
projections are laid out by KIND, ``[q | k | v | z | b | a]`` and ``[q |
k | v | g]``, where the checkpoint interleaves them per key head (a
permutation of the rows of seeded matrices); expert matrices are stacked
``(E, d, f)`` / ``(E, f, d)`` and each held expert is applied to every
position with its weight (0 where it was not chosen); attention is
computed in blocks of query positions so that two thousand positions fit
beside a serving tenant.  Not run: multi-token prediction, the router's
auxiliary loss, dropout.
"""
import functools

import jax
import jax.numpy as jnp

LINEAR = ("ln1_gamma", "inproj_weight", "conv_weight", "dt_bias", "A_log",
          "gnorm_gamma", "outproj_weight")
ATTENTION = ("ln1_gamma", "qkv_weight", "qnorm_gamma", "knorm_gamma",
             "out_weight")
ROUTED = ("ln2_gamma", "router_weight", "gate_weight", "up_weight",
          "down_weight", "shared_gate_weight", "shared_up_weight",
          "shared_down_weight", "shared_score_weight")
QUERY_BLOCK = 256
L2_EPS = 1e-6


def _w(stored):
    """The published `w` of a gain the model under test stores as `1 + w`."""
    return stored - 1.0


def _rms(x, w, eps):
    """``RMS(x; w)``: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _rotary(x, theta, rotary_dim):
    """``x (heads, T, d_head)``: the first `rotary_dim` channels of each
    head rotate-half among themselves, row t at position t; the others
    pass."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "d_head",
                                             "rotary_dim", "eps", "theta"))
def attention_mixer(x, ln1_gamma, qkv_weight, qnorm_gamma, knorm_gamma,
                    out_weight, heads, kv_heads, d_head, rotary_dim, eps,
                    theta):
    """``x + Attn(RMS_in(x))`` for ``x (T, d)``."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        qw, kw = heads * d_head, kv_heads * d_head
        proj = _rms(x, _w(ln1_gamma), eps) @ qkv_weight.T
        q, k, v, gate = jnp.split(proj, (qw, qw + kw, qw + 2 * kw), axis=-1)
        q = _rms(q.reshape(t, heads, d_head), _w(qnorm_gamma), eps)
        k = _rms(k.reshape(t, kv_heads, d_head), _w(knorm_gamma), eps)
        q, k = q.transpose(1, 0, 2), k.transpose(1, 0, 2)
        v = v.reshape(t, kv_heads, d_head).transpose(1, 0, 2)
        q, k = (_rotary(part, theta, rotary_dim) for part in (q, k))
        # each K/V head repeated for its group of query heads
        k, v = (jnp.repeat(part, heads // kv_heads, axis=0) for part in (k, v))
        out = []
        for start in range(0, t, QUERY_BLOCK):   # blocks of query positions
            i = jnp.arange(start, min(start + QUERY_BLOCK, t))[:, None]
            s = jnp.einsum("hqd,hkd->hqk", q[:, start:start + QUERY_BLOCK],
                           k) / jnp.sqrt(float(d_head))
            s = jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1),
                                  v))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, qw)
        return x + (o * jax.nn.sigmoid(gate)) @ out_weight.T


@functools.partial(jax.jit, static_argnames=("key_heads", "heads", "key_dim",
                                             "value_dim", "eps"))
def linear_mixer(x, ln1_gamma, inproj_weight, conv_weight, dt_bias, A_log,
                 gnorm_gamma, outproj_weight, key_heads, heads, key_dim,
                 value_dim, eps):
    """``x + GatedDeltaNet(RMS_in(x))`` for ``x (T, d)``, the rule
    position by position."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        qk, vz = key_heads * key_dim, heads * value_dim
        proj = _rms(x, _w(ln1_gamma), eps) @ inproj_weight.T
        raw, z, b, a = jnp.split(
            proj, (2 * qk + vz, 2 * qk + 2 * vz, 2 * qk + 2 * vz + heads),
            axis=-1)
        taps = conv_weight.shape[0]
        padded = jnp.pad(raw, ((taps - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[j:j + t] * conv_weight[j]
                              for j in range(taps)))

        def l2(part):
            part = part.reshape(t, key_heads, key_dim)
            part = part * jax.lax.rsqrt(
                jnp.sum(jnp.square(part), axis=-1, keepdims=True) + L2_EPS)
            # value head n takes q/k head n // (heads / key_heads)
            return jnp.repeat(part, heads // key_heads, axis=1)

        q = l2(qkv[:, :qk]) / key_dim ** 0.5
        k = l2(qkv[:, qk:2 * qk])
        v = qkv[:, 2 * qk:].reshape(t, heads, value_dim)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(A_log) * jax.nn.softplus(a + dt_bias)

        def step(s, inp):   # one position: s (heads, key_dim, value_dim)
            q_t, k_t, v_t, beta_t, g_t = inp
            s = jnp.exp(g_t)[:, None, None] * s
            u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(
            step, jnp.zeros((heads, key_dim, value_dim), x.dtype),
            (q, k, v, beta, g))
        # the output norm's gain is PLAIN: gamma, not 1 + gamma
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + eps) * gnorm_gamma
        y = (o * jax.nn.silu(z.reshape(t, heads, value_dim))).reshape(t, vz)
        return x + y @ outproj_weight.T


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router_weight, top_k, norm_topk):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,): how far the last chosen expert's probability lies above
    the first one left out, as a share of itself)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router_weight, axis=-1)
    ranked = jnp.argsort(-probs, axis=-1)
    best = jnp.take_along_axis(probs, ranked[:, :top_k + 1], axis=-1)
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ranked[:, :top_k]].set(1.0)
    weights = probs * chosen
    if norm_topk:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1]


def expert_layer(x, router_weight, gate_weight, up_weight, down_weight,
                 shared, shared_score, top_k, norm_topk, first):
    """The expert layer's output for normed input `x (T, d)`: the routed
    sum over the experts whose matrices are given — experts `first` ..
    `first + count` of the router's E — plus, where `shared` ``(gate, up,
    down)`` is not None, ``sigmoid(x shared_score)`` times the shared
    expert.  Returns (y, margin)."""
    weights, margin = route(x, router_weight, top_k, norm_topk)
    count = gate_weight.shape[0]
    # (the weights are float32; the sum runs in the dtype of `x`)
    mine = weights[:, first:first + count].astype(x.dtype)

    def one(y, expert):       # every position through one expert, weighted
        gate, up, down, w = expert
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (gate_weight, up_weight, down_weight, mine.T))
    if shared is not None:
        y = y + jax.nn.sigmoid(x @ shared_score) * _swiglu(x, *shared)
    return y, margin


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk", "first",
                                             "eps"))
def routed_block(x, ln2_gamma, router_weight, gate_weight, up_weight,
                 down_weight, shared_gate_weight, shared_up_weight,
                 shared_down_weight, shared_score_weight, top_k, norm_topk,
                 first, eps):
    """``x + MoE(RMS_post(x))``."""
    with jax.default_matmul_precision("highest"):
        y, margin = expert_layer(
            _rms(x, _w(ln2_gamma), eps), router_weight, gate_weight,
            up_weight, down_weight,
            (shared_gate_weight, shared_up_weight, shared_down_weight),
            shared_score_weight, top_k, norm_topk, first)
        return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, _w(gamma), eps) @ head.T


def forward(params, config, tokens, rows=None, dtype=None):
    """One sequence: (logits at the positions `rows` (default all) over
    the vocabulary `params` holds, margins (layers, T)).  `dtype`: THE
    CONTROL — every weight cast to it as it is used, so that activations
    and state are of it too (the router's product stays float32 of the
    cast operands) — which the family's check has to refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    x = cast(params["embed_weight"][jnp.asarray(tokens, jnp.int32)])
    eps = float(config["rms_norm_eps"])
    first = config.get("held_experts", (0, None))[0]
    d_head = config["head_dim"]
    margins = []
    for i in range(config["num_hidden_layers"]):
        layer = lambda names: [cast(params["l%d_%s" % (i, n)])  # noqa: E731
                               for n in names]
        if (i + 1) % config["full_attention_interval"]:
            x = linear_mixer(
                x, *layer(LINEAR), key_heads=config["linear_num_key_heads"],
                heads=config["linear_num_value_heads"],
                key_dim=config["linear_key_head_dim"],
                value_dim=config["linear_value_head_dim"], eps=eps)
        else:
            x = attention_mixer(
                x, *layer(ATTENTION), heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"], d_head=d_head,
                rotary_dim=int(d_head * config["partial_rotary_factor"]),
                eps=eps, theta=float(config["rope_theta"]))
        x, margin = routed_block(
            x, *layer(ROUTED), top_k=config["num_experts_per_tok"],
            norm_topk=bool(config["norm_topk_prob"]), first=first, eps=eps)
        margins.append(margin)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return (_head(x, cast(params["ln_f_gamma"]), cast(params["head_weight"]),
                  eps),
            jnp.stack(margins))


def logits(params, config, tokens):
    return forward(params, config, tokens)[0]
