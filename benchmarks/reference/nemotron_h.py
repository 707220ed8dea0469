"""Plain reference for the `nemotron_h` family: NVIDIA's Nemotron-H decoder
(`nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16` config.json, `model_type:
nemotron_h`, 31.6B-A3.2B; equations as `modeling_nemotron_h.py` of the
transformers library has them; Mamba-2: Dao & Gu, arXiv:2405.21060) as one
full forward pass in straightforward float32 `jax.numpy` at "highest"
matmul precision — no cache, no batching, no chunked scan, no kernels.
Independent of `mxnet_tpu`: only the parameter names and layouts follow the
model under test.

`hybrid_override_pattern` is a list of SUBLAYERS, one character each, and a
published layer is ONE of them with ONE norm:

    h0 = Emb[token]                                       (no multiplier)
    layer i of kind c:   h <- h + F_c(RMSNorm_i(h))       (plain gain, eps
                                                  `layer_norm_epsilon`)
    logits = RMSNorm_f(h_L) W_head^T                      (untied head)

No bias anywhere but the conv's.

`M`, Mamba-2 — ``d_inner = mamba_num_heads x mamba_head_dim`` (64 x 64 =
4,096: the model's code sets the inner width from the heads, NOT `expand` x
`hidden_size`), G = `n_groups` (8) groups of S = `ssm_state_size` (128)
states, K = `conv_kernel` (4) taps:

    [z (d_inner) | xBC (d_inner + 2 G S) | dt (H)] = W_in x
    xBC = silu(causal depthwise conv_K(xBC) + b)
    dt  = softplus(dt + dt_bias)            (no clamp: `time_step_limit`
                                             is (0, inf))
    a   = -exp(A_log)                                     per head
    head j reads group floor(j / (H / G))'s B_t, C_t
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t
    y   = y * silu(z); EACH of the G groups of d_inner / G channels times
          rsqrt(mean of its own squares + eps); times the gain (d_inner,)
    F_M = W_out y

`*`, attention — ``q = W_q x`` (32 heads x 128), ``k, v`` (2 heads x 128),
NO position signal (`NemotronHAttention` turns nothing: `rope_theta` and
`partial_rotary_factor` are not read), causal ``softmax(q k^T / sqrt(128))
v``, K/V head g read by query heads ``16 g .. 16 g + 15``, ``W_o``.

`E`, experts — UNGATED, two matrices an expert, a squared ReLU:

    s = sigmoid(W_r x)                   float32, 128 wide, no bias
    chosen = the 6 largest of s + e_score_correction_bias   (`n_group` =
             `topk_group` = 1: no group limit)
    w = s[chosen] / (sum of s[chosen] + 1e-20) x routed_scaling_factor
    F_E = sum_e w_e W2_e relu(W1_e x)^2  +  V2 relu(V1 x)^2
          (the shared expert of width `moe_shared_expert_intermediate_size`,
           every token, unweighted)

A CHIP'S SHARE (`held` ``(first, count)``, by default the configuration's
`held_experts`; None = the whole layer): the router, the choice and the
weights stay 128 wide and keep six; only the terms of the experts ``first
.. first + count`` are summed, and what the others would add is left out —
neither computed nor stood in for.  The shared expert, the mixers and the
attention are computed whole.  A sliced vocabulary is a smaller one.

Departures from the published code, none in the mathematics: Q, K and V are
one fused ``[q | k | v]`` matrix; expert matrices are stacked ``up (E', d,
f)`` / ``down (E', f, d)`` and may be stored WIDER than `f`, padded for a
device's tiles — the published `moe_intermediate_size` columns of `up` and
rows of `down` alone are read; each held expert is applied to the positions
that chose it (found on the host, padded to whole `EXPERT_PAD`s with weight
0), one call an expert; attention goes in blocks of `QUERY_BLOCK` query
positions and the head over the asked rows alone; the recurrence is a
`lax.scan` over positions.  Not run: dropout, the router's bias update,
`rescale_prenorm_residual` (an initialisation), `residual_in_fp32`.

`fault`, one of `FAULTS`: what a wrong program would compute, for the tests
and the family's controls.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

MAMBA = ("ln1_gamma", "inproj_weight", "conv_weight", "conv_bias", "dt_bias",
         "A_log", "D", "mnorm_gamma", "outproj_weight")
ATTENTION = ("ln1_gamma", "qkv_weight", "out_weight")
ROUTED = ("ln2_gamma", "router_weight", "router_bias", "up_weight",
          "down_weight", "shared_up_weight", "shared_down_weight")
KINDS = {"M": MAMBA, "*": ATTENTION, "E": ROUTED}
FAULTS = ("gated", "relu", "norm_one_group", "heads_mod_groups", "rotary",
          "no_route_scale", "bias_in_weights", "not_renormalised", "softmax",
          "no_shared", "norm_before_gate")
QUERY_BLOCK = 256
EXPERT_PAD = 256


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def _relu2(x, fault=None):
    return jax.nn.relu(x) if fault == "relu" else jnp.square(jax.nn.relu(x))


def mamba_sizes(config):
    return dict(heads=config["mamba_num_heads"], dim=config["mamba_head_dim"],
                state=config["ssm_state_size"], groups=config["n_groups"],
                eps=float(config["layer_norm_epsilon"]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "dim", "state", "groups", "eps", "fault", "projection"))
def mamba(x, ln1_gamma, inproj_weight, conv_weight, conv_bias, dt_bias, A_log,
          D, mnorm_gamma, outproj_weight, heads, dim, state, groups, eps,
          fault=None, projection="highest", length=None):
    """``(F_M(RMS(x)), conv window, final state)`` for ``x (T, d)``: the
    window is the last ``K - 1`` raw ``xBC`` rows (zeros before the
    sequence).  `projection`: the in-projection's matmul precision (the
    family's state limits give the reference the program's one pass).
    `length`: window and state as of that many positions of a PADDED `x` —
    positions from there on take ``dt = 0``, decay 1 and no input, so the
    state stays where position ``length - 1`` left it (one compiled scan
    then serves every sequence of a padded length)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        d_inner, taps = heads * dim, conv_weight.shape[0]
        proj = jnp.dot(_rms(x, ln1_gamma, eps), inproj_weight.T,
                       precision=projection)
        z, xbc_raw, dt = (proj[:, :d_inner], proj[:, d_inner:-heads],
                          proj[:, -heads:])
        padded = jnp.pad(xbc_raw, ((taps - 1, 0), (0, 0)))
        xbc = conv_bias + sum(padded[j:j + t] * conv_weight[j]
                              for j in range(taps))
        xbc = jax.nn.silu(xbc)
        xs = xbc[:, :d_inner].reshape(t, heads, dim)
        b = xbc[:, d_inner:d_inner + groups * state].reshape(t, groups, state)
        c = xbc[:, d_inner + groups * state:].reshape(t, groups, state)
        of_head = (jnp.arange(heads) % groups if fault == "heads_mod_groups"
                   else jnp.arange(heads) // (heads // groups))
        dt = jax.nn.softplus(dt + dt_bias)
        if length is not None:
            dt = jnp.where(jnp.arange(t)[:, None] < length, dt, 0.0)
        a = -jnp.exp(A_log)

        def step(s, at):
            x_t, b_t, c_t, dt_t = at
            s = (jnp.exp(dt_t * a)[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[of_head][:, None])
            return s, jnp.einsum("hps,hs->hp", s, c_t[of_head])

        final, y = jax.lax.scan(
            step, jnp.zeros((heads, dim, state), x.dtype), (xs, b, c, dt))
        y = (y + D[:, None] * xs).reshape(t, d_inner)
        runs = 1 if fault == "norm_one_group" else groups
        if fault == "norm_before_gate":
            y = _rms(y.reshape(t, runs, -1), 1.0, eps).reshape(t, d_inner)
            y = y * jax.nn.silu(z) * mnorm_gamma
        else:
            y = y * jax.nn.silu(z)
            y = _rms(y.reshape(t, runs, -1), 1.0, eps).reshape(t, d_inner)
            y = y * mnorm_gamma
        window = jax.lax.dynamic_slice_in_dim(
            padded, t if length is None else length, taps - 1, 0)
        return y @ outproj_weight.T, window, final


def _rotary(x, theta):
    """``x (H, T, r)`` turned rotate-half, row t at position t (the
    `rotary` fault alone: the model has no position signal)."""
    r = x.shape[-1]
    freqs = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    one, two = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([one * cos - two * sin, two * cos + one * sin],
                           axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "fault"))
def attention(x, ln1_gamma, qkv_weight, out_weight, heads, kv_heads, eps,
              fault=None):
    """``F_*(RMS(x))`` for ``x (T, d)``."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        dh = out_weight.shape[1] // heads
        qkv = _rms(x, ln1_gamma, eps) @ qkv_weight.T
        q = qkv[:, :heads * dh].reshape(t, heads, dh).transpose(1, 0, 2)
        k, v = (qkv[:, at:at + kv_heads * dh].reshape(t, kv_heads, dh)
                .transpose(1, 0, 2)
                for at in (heads * dh, (heads + kv_heads) * dh))
        if fault == "rotary":
            q, k = _rotary(q, 10000.0), _rotary(k, 10000.0)
        k, v = (jnp.repeat(m, heads // kv_heads, axis=0) for m in (k, v))
        block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

        def rows(start):     # a block of query positions against every key
            i = start + jnp.arange(block)
            s = jnp.einsum("hqd,hkd->hqk", jax.lax.dynamic_slice_in_dim(
                q, start, block, 1), k) * dh ** -0.5
            s = jnp.where(jnp.arange(t)[None, :] <= i[:, None], s, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(rows, jnp.arange(0, t, block))    # (blocks, H, b, dh)
        o = o.transpose(0, 2, 1, 3).reshape(t, heads * dh)
        return o @ out_weight.T


def route(x, router_weight, router_bias, top_k, scale, first=0, count=None,
          fault=None):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,)): how far the choice among the experts `first` .. `first +
    count` (default all) lies from changing — the least distance of one of
    THEIR biased scores from the edge of the choice (the first left out for
    an expert that is chosen, the last chosen for one that is not) as a
    share of the last chosen score.  A tie between two experts of other
    chips moves no term of this range's sum, only the divisor."""
    logits = x.astype(jnp.float32) @ router_weight.astype(jnp.float32)
    s = (jax.nn.softmax(logits, axis=-1) if fault == "softmax"
         else jax.nn.sigmoid(logits))
    biased = s + router_bias.astype(jnp.float32)
    ranked = jnp.argsort(-biased, axis=-1)
    at = jnp.arange(x.shape[0])[:, None]
    chosen = jnp.zeros(s.shape, bool).at[at, ranked[:, :top_k]].set(True)
    weights = jnp.where(chosen, biased if fault == "bias_in_weights" else s,
                        0.0)
    if fault != "not_renormalised":
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        weights = weights * scale
    best = jnp.take_along_axis(biased, ranked[:, :top_k + 1], axis=-1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    last_score = jnp.take_along_axis(s, ranked[:, top_k - 1:top_k], -1)
    from_edge = jnp.where(chosen, biased - first_out, last_in - biased)
    mine = slice(first, None if count is None else first + count)
    return weights, (from_edge[:, mine] / last_score).min(axis=-1)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "first",
                                             "count", "eps", "fault"))
def _routed_in(x, ln2_gamma, router_weight, router_bias, top_k, scale, first,
               count, eps, fault):
    with jax.default_matmul_precision("highest"):
        normed = _rms(x, ln2_gamma, eps)
        weights, margin = route(normed, router_weight, router_bias, top_k,
                                scale, first, count, fault)
        return normed, weights[:, first:first + count], margin


@functools.partial(jax.jit, static_argnames=("width", "fault"))
def _expert(y, x, at, w, up_weight, down_weight, e, width, fault=None):
    """`y` with ``w * W2_e relu(W1_e x[at])^2`` added at the rows `at`:
    expert `e` of the stacks, `width` of its hidden channels."""
    with jax.default_matmul_precision("highest"):
        rows = x[at]
        up, down = up_weight[e][:, :width], down_weight[e][:width]
        h = _relu2(rows @ up, fault)
        if fault == "gated":   # a gate's product: the NEXT expert's matrix
            gate = up_weight[(e + 1) % up_weight.shape[0]][:, :width]
            h = jax.nn.relu(rows @ up) * (rows @ gate)
        return y.at[at].add(w[:, None].astype(y.dtype) * (h @ down))


@functools.partial(jax.jit, static_argnames=("fault",))
def _shared(x, up, down, fault=None):
    with jax.default_matmul_precision("highest"):
        return _relu2(x @ up, fault) @ down


def expert_layer(x, ln2_gamma, router_weight, router_bias, up_weight,
                 down_weight, shared_up_weight, shared_down_weight, top_k,
                 scale, first, eps, width=None, fault=None, shared_times=1.0):
    """``(F_E(RMS(x)), margin (T,))`` for ``x (T, d)``: the routed sum over
    the experts whose matrices are given — experts `first` .. `first +
    count` of the router's E, `width` (default all) of each matrix's hidden
    channels — plus `shared_times` (1: once) the shared expert's term."""
    count = up_weight.shape[0]
    normed, mine, margin = _routed_in(
        x, ln2_gamma, router_weight, router_bias, top_k, float(scale),
        int(first), count, eps, fault)
    y = jnp.zeros_like(normed)
    if isinstance(mine, jax.core.Tracer):
        # under `jax.grad` nothing is known on the host: every position
        # goes through every held expert, at its weight (0 where not chosen)
        every = jnp.arange(x.shape[0])
        for e in range(count):
            y = _expert(y, normed, every, mine[:, e], up_weight, down_weight,
                        e, width, fault)
    else:
        # on the host, and padded there (with row 0 at weight 0, which adds
        # an exact 0): the device sees whole `EXPERT_PAD`s of rows alone, a
        # few shapes, not one program a count of rows
        weights = np.asarray(mine)                    # (T, count)
        for e in np.flatnonzero((weights > 0).any(axis=0)):
            at = np.flatnonzero(weights[:, e] > 0)
            pad = -len(at) % EXPERT_PAD
            y = _expert(y, normed, np.pad(at, (0, pad)),
                        np.pad(weights[at, e], (0, pad)), up_weight,
                        down_weight, int(e), width, fault)
    if shared_times and fault != "no_shared":
        y = y + shared_times * _shared(normed, shared_up_weight,
                                       shared_down_weight, fault)
    return y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def pattern(config):
    return config["hybrid_override_pattern"]


def sublayer(h, params, config, i, cast=lambda w: w, held="config",
             fault=None, projection="highest"):
    """Published layer i on the stream ``h (T, d)``: ``(what it adds,
    margin (T,) — inf where the layer routes nothing)``."""
    kind = pattern(config)[i]
    eps = float(config["layer_norm_epsilon"])
    weights = [cast(params["l%d_%s" % (i, n)]) for n in KINDS[kind]]
    no_margin = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    if kind == "M":
        return mamba(h, *weights, **mamba_sizes(config), fault=fault,
                     projection=projection)[0], no_margin
    if kind == "*":
        return attention(h, *weights, heads=config["num_attention_heads"],
                         kv_heads=config["num_key_value_heads"], eps=eps,
                         fault=fault), no_margin
    if held == "config":
        held = config.get("held_experts")
    return expert_layer(h, *weights, top_k=config["num_experts_per_tok"],
                        scale=config["routed_scaling_factor"],
                        first=0 if held is None else int(held[0]), eps=eps,
                        width=config["moe_intermediate_size"], fault=fault)


def first_mixer_state(params, config, tokens, length=None):
    """What layer 0, a Mamba-2 layer, keeps after `tokens` (after the first
    `length` of them, where they are padded): ``(conv window, state)`` with
    the layer's input projection at the device's default precision (the
    program's one bfloat16 pass on a TPU) and everything after it float32
    at "highest": only the conv and the scan then lie between the
    program's state and this one."""
    assert pattern(config)[0] == "M", pattern(config)
    x = params["embed_weight"][jnp.asarray(tokens, jnp.int32)]
    _, window, state = mamba(
        x, *[params["l0_" + n] for n in MAMBA], **mamba_sizes(config),
        projection="default",
        length=None if length is None else jnp.int32(length))
    return window, state


def forward(params, config, tokens, rows=None, dtype=None, held="config",
            fault=None):
    """One sequence: (logits at the positions `rows` (default all) over the
    vocabulary `params` holds, margins (routed layers, T)).  `held`
    ``(first, count)``: the experts whose matrices `params` holds (default:
    the configuration's `held_experts`; None: every expert).  `dtype`: THE
    CONTROL — every weight cast to it as it is used, so that activations
    and state are of it too (the router's product stays float32 of the
    cast operands) — which the family's check has to refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    h = cast(params["embed_weight"])[jnp.asarray(tokens, jnp.int32)]
    margins = []
    for i, kind in enumerate(pattern(config)):
        f, margin = sublayer(h, params, config, i, cast, held, fault)
        # (waited for: a layer's temporaries leave before the next one's
        # stand beside a serving tenant)
        h = jax.block_until_ready(h + f.astype(h.dtype))
        if kind == "E":
            margins.append(margin)
    if rows is not None:
        h = h[jnp.asarray(rows, jnp.int32)]
    return (_head(h, cast(params["ln_f_gamma"]), cast(params["head_weight"]),
                  float(config["layer_norm_epsilon"])),
            jnp.stack(margins) if margins else jnp.zeros((0, len(tokens))))


def logits(params, config, tokens, held="config"):
    return forward(params, config, tokens, held=held)[0]


def loss(params, config, tokens, labels, ignore=-1):
    """Mean cross-entropy of `labels (T,)` (positions labelled `ignore`
    left out) under `forward`: what `training_symbol` minimises."""
    labels = jnp.asarray(labels, jnp.int32)
    logp = jax.nn.log_softmax(logits(params, config, tokens, held=None))
    keep = labels != ignore
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)
    return -(picked[:, 0] * keep).sum() / keep.sum()
