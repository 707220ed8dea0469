"""Plain reference for the `granite_hybrid` family: the Granite 4.0-H
decoder (`ibm-granite/granite-4.0-h-micro` config.json, `model_type:
granitemoehybrid`; Mamba-2 mixer of Dao & Gu, arXiv:2405.21060) as one
full forward pass in straightforward float32 `jax.numpy` at "highest"
matmul precision — no cache, no batching, no chunking, no kernels: the
recurrence is a `lax.scan` over POSITIONS, attention a full causal
softmax with each K/V head repeated for its query heads.  Independent of
`mxnet_tpu`: only the parameter names and layouts follow the model under
test.  It computes in the dtype of the parameters it is given: float32
for every caller that judges; PERF.md's reading of "the reference in the
precision below" hands it bfloat16 parameters.  `first_mixer_state` is
the one place that departs from "highest", and says why.

    h = embedding_multiplier * embed[tok]
    per layer:  h += residual_multiplier * mixer(RMSNorm(h))
                [a | b] = W_in RMSNorm(h)
                h += residual_multiplier * W_out (silu(a) * b)
    logits = RMSNorm(h) embed^T / logits_scaling

Mamba-2 mixer (64 heads x 64, one group of 128 states, conv of 4 taps):

    [z | xBC | dt] = W_inproj x          xBC = [x | B | C]
    xBC = silu(causal depthwise conv1d_4(xBC) + conv_bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)              per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          (heads, 64, 128)
    y_t = S_t C_t + D x_t
    out = W_outproj (RMSNorm(y * silu(z)) * w)   (gate first, then the
                                                  norm over all 4096)

Attention mixer: `softmax(attention_multiplier * q k^T + causal) v`, 32
query heads over 8 K/V heads, no position signal of any kind
(`position_embedding_type: "nope"`).

Departures from the published implementation: none in the mathematics.
Left out because the configuration switches them off or they do not
touch a forward pass: projection biases (`mamba_proj_bias`,
`attention_bias` false), dropout (0), the routed experts
(`num_local_experts` 0 — only the shared MLP exists), `time_step_limit`
(0, inf), the cache.

Layouts, as the model under test holds them (the configuration file
lists them under `assumed`): Q, K and V are one fused ``((32 + 2 x 8) x
64, d)`` matrix `[q | k | v]`; the MLP's input matrix is ``(2 ff, d)``
`[a | b]`; the conv weight is ``(taps, channels)``, tap j multiplying
position ``t - 3 + j``.
"""
import functools

import jax
import jax.numpy as jnp

MAMBA_PARAMS = ("ln1_gamma", "inproj_weight", "conv_weight", "conv_bias",
                "dt_bias", "A_log", "D", "mnorm_gamma", "outproj_weight",
                "ln2_gamma", "ffn1_weight", "ffn2_weight")
ATTENTION_PARAMS = ("ln1_gamma", "qkv_weight", "out_weight", "ln2_gamma",
                    "ffn1_weight", "ffn2_weight")


def _rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def _mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps, residual):
    a, b = jnp.split(_rms(x, ln2_gamma, eps) @ ffn1_weight.T, 2, axis=-1)
    return x + residual * ((jax.nn.silu(a) * b) @ ffn2_weight.T)


# one program per layer kind: jitted once, called per layer with that
# layer's weights, so the reference compiles in seconds at any depth
@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "state",
                                             "groups", "eps", "residual",
                                             "projection"))
def _mamba_layer(x, ln1_gamma, inproj_weight, conv_weight, conv_bias, dt_bias,
                 A_log, D, mnorm_gamma, outproj_weight, ln2_gamma,
                 ffn1_weight, ffn2_weight, heads, head_dim, state, groups,
                 eps, residual, projection=None):
    """One Mamba layer over ``x (T, d)``: the layer's output, and what a
    cache would keep of it after the last position — the last ``taps -
    1`` rows of the raw ``xBC`` (zeros before the sequence) and the
    state ``S_T``.  `projection` is the precision of the input
    projection alone; None is "highest" like everything else."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        d_inner, gs = heads * head_dim, groups * state
        proj = jnp.matmul(_rms(x, ln1_gamma, eps), inproj_weight.T,
                          precision=projection)
        z = proj[:, :d_inner]
        xbc = proj[:, d_inner:2 * d_inner + 2 * gs]
        dt = jax.nn.softplus(proj[:, 2 * d_inner + 2 * gs:] + dt_bias)
        taps = conv_weight.shape[0]
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[j:j + t] * conv_weight[j]
                              for j in range(taps)) + conv_bias)
        xs = xbc[:, :d_inner].reshape(t, heads, head_dim)
        rep = heads // groups
        b = jnp.repeat(xbc[:, d_inner:d_inner + gs].reshape(t, groups, state),
                       rep, axis=1)
        c = jnp.repeat(xbc[:, d_inner + gs:].reshape(t, groups, state),
                       rep, axis=1)
        a = -jnp.exp(A_log)

        def step(s, inp):  # one position: s (heads, head_dim, state)
            x_t, b_t, c_t, dt_t = inp
            s = (jnp.exp(dt_t * a)[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D[:, None] * x_t

        last, y = jax.lax.scan(
            step, jnp.zeros((heads, head_dim, state), x.dtype),
            (xs, b, c, dt))
        y = _rms(y.reshape(t, d_inner) * jax.nn.silu(z), mnorm_gamma, eps)
        x = x + residual * (y @ outproj_weight.T)
        return (_mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps, residual),
                padded[t:], last)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "eps", "residual"))
def _attention_layer(x, ln1_gamma, qkv_weight, out_weight, ln2_gamma,
                     ffn1_weight, ffn2_weight, heads, kv_heads, scale, eps,
                     residual):
    with jax.default_matmul_precision("highest"):
        t, d = x.shape
        dh = d // heads
        qkv = _rms(x, ln1_gamma, eps) @ qkv_weight.T
        q = qkv[:, :d].reshape(t, heads, dh).transpose(1, 0, 2)
        k = qkv[:, d:d + kv_heads * dh].reshape(t, kv_heads, dh)
        v = qkv[:, d + kv_heads * dh:].reshape(t, kv_heads, dh)
        k, v = (jnp.repeat(part.transpose(1, 0, 2), heads // kv_heads, axis=0)
                for part in (k, v))
        scores = scale * jnp.einsum("hqd,hkd->hqk", q, k)
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + residual * (ctx.transpose(1, 0, 2).reshape(t, d)
                            @ out_weight.T)
        return _mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps, residual)


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, gamma, embed, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ embed.T / scaling


def _mamba_sizes(config):
    return dict(heads=config["mamba_n_heads"],
                head_dim=config["mamba_d_head"],
                state=config["mamba_d_state"],
                groups=config["mamba_n_groups"],
                eps=float(config["rms_norm_eps"]),
                residual=float(config["residual_multiplier"]))


def _embed(params, config, tokens):
    return (float(config["embedding_multiplier"])
            * params["embed_weight"][jnp.asarray(tokens, jnp.int32)])


def first_mixer_state(params, config, tokens):
    """What layer 0, a Mamba layer, keeps after `tokens`: ``(conv window
    (taps - 1, channels), state (heads, head_dim, d_state))``, with the
    layer's INPUT PROJECTION multiplied at the device's default precision
    — as the configuration states the model under test multiplies its
    projections (one bfloat16 pass on a TPU, float32 on a CPU) — and
    everything after it float32 at "highest", position by position.
    Layer 0's input is the embedding, so nothing else of the model under
    test's arithmetic comes before this state: what is left between its
    layer-0 state and this one is the conv and the recurrence alone."""
    assert config["layer_types"][0] == "mamba", config["layer_types"][0]
    _, window, state = _mamba_layer(
        _embed(params, config, tokens),
        *(params["l0_" + n] for n in MAMBA_PARAMS), projection="default",
        **_mamba_sizes(config))
    return window, state


def logits(params, config, tokens, last=None):
    """One sequence: logits ``(T, vocab)`` at every position, or at the
    last `last` positions only (a long context's head is 0.4 MB a row)."""
    x = _embed(params, config, tokens)
    eps = float(config["rms_norm_eps"])
    residual = float(config["residual_multiplier"])
    for i, kind in enumerate(config["layer_types"]):
        if kind == "mamba":
            x, _, _ = _mamba_layer(
                x, *(params["l%d_%s" % (i, n)] for n in MAMBA_PARAMS),
                **_mamba_sizes(config))
        else:
            x = _attention_layer(
                x, *(params["l%d_%s" % (i, n)] for n in ATTENTION_PARAMS),
                heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                scale=float(config["attention_multiplier"]), eps=eps,
                residual=residual)
    if last is not None:
        x = x[-int(last):]
    return _head(x, params["ln_f_gamma"], params["embed_weight"], eps,
                 float(config["logits_scaling"]))
