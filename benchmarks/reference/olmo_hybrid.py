"""Plain reference for the `olmo_hybrid` family: the Olmo-Hybrid decoder
(`allenai/Olmo-Hybrid-7B` config.json, `model_type: olmo_hybrid`; Gated
DeltaNet mixer of Yang, Kautz & Hatamizadeh, arXiv:2412.06464; the block
of Olmo 2, arXiv:2501.00656) as one full forward pass in straightforward
float32 `jax.numpy` at "highest" matmul precision — no cache, no
batching, no chunks, no kernels: the delta rule is a `lax.scan` over
POSITIONS, attention a full causal softmax.  Independent of `mxnet_tpu`:
only the parameter names and layouts follow the model under test.  It
computes in the dtype of the parameters it is given: float32 for every
caller that judges; PERF.md's reading of "the reference in the precision
below" hands it bfloat16 parameters.  `first_mixer_state` is the one
place that departs from "highest", and says why.

    h = embed[tok]
    per layer:  h += RMSNorm(mixer(h))            the branch's OUTPUT is
                [a | b] = W_in h                  normed, not its input
                h += RMSNorm(W_out (silu(a) * b))
    logits = RMSNorm(h) head^T

Gated DeltaNet mixer (30 heads, keys of 96, values of 192, conv of 4):

    [q | k | v | z | b | a] = W_inproj x
    [q | k | v] = silu(causal depthwise conv1d_4([q | k | v]))   no bias
    q = q / sqrt(|q|^2 + 1e-6) / sqrt(96);  k = k / sqrt(|k|^2 + 1e-6)
    beta = 2 sigmoid(b)                      (linear_allow_neg_eigval)
    alpha = exp(-exp(A_log) softplus(a + dt_bias))               per head
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                                     S (heads, 192, 96)
    out = W_outproj concat_h(RMSNorm_192(o_h) * gamma * silu(z_h))

Attention mixer: `softmax(q k^T / sqrt(128) + causal) v`, 30 heads x 128,
RMSNorm over the WHOLE q and k projections before the heads are split,
no position signal of any kind (`rope_parameters.rope_theta` is null).

Departures from the published description: none in the mathematics that
config.json states.  The config has no key for the block's norm
placement: it is Olmo 2's / Olmo 3's published block (the configuration
file lists it under `assumed`, with the 1e-6 inside the root of the L2
norm, which is the Gated DeltaNet authors' implementation's).  Left out
because they do not touch a forward pass: dropout, the cache.

Layouts, as the model under test holds them (`assumed` lists them): the
mixer's input projection is one fused matrix `[q | k | v | z | b | a]`;
Q, K and V of an attention layer one fused ``(3 d, d)`` matrix `[q | k |
v]`; the MLP's input matrix ``(2 ff, d)`` `[a | b]`; the conv weight
``(taps, channels)``, tap j multiplying position ``t - 3 + j``.
"""
import functools

import jax
import jax.numpy as jnp

LINEAR_PARAMS = ("ln1_gamma", "inproj_weight", "conv_weight", "dt_bias",
                 "A_log", "gnorm_gamma", "outproj_weight", "ln2_gamma",
                 "ffn1_weight", "ffn2_weight")
ATTENTION_PARAMS = ("ln1_gamma", "qkv_weight", "qnorm_gamma", "knorm_gamma",
                    "out_weight", "ln2_gamma", "ffn1_weight", "ffn2_weight")
L2_EPS = 1e-6


def _rms(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def _mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps):
    a, b = jnp.split(x @ ffn1_weight.T, 2, axis=-1)
    return x + _rms((jax.nn.silu(a) * b) @ ffn2_weight.T, ln2_gamma, eps)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def _gated_norm(o, z, gamma, eps):
    """Each head's output normed over its own values, times the gate."""
    return _rms(o, gamma, eps) * jax.nn.silu(z)


# one program per layer kind: jitted once, called per layer with that
# layer's weights, so the reference compiles in seconds at any depth
@functools.partial(jax.jit, static_argnames=("heads", "key_dim", "value_dim",
                                             "beta_scale", "eps",
                                             "projection"))
def _linear_layer(x, ln1_gamma, inproj_weight, conv_weight, dt_bias, A_log,
                  gnorm_gamma, outproj_weight, ln2_gamma, ffn1_weight,
                  ffn2_weight, heads, key_dim, value_dim, beta_scale, eps,
                  projection=None):
    """One Gated DeltaNet layer over ``x (T, d)``: the layer's output,
    and what a cache would keep of it after the last position — the last
    ``taps - 1`` rows of the raw ``[q | k | v]`` (zeros before the
    sequence) and the state ``S_T (heads, value_dim, key_dim)``.
    `projection` is the precision of the input projection alone; None is
    "highest" like everything else."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        qk, vz = heads * key_dim, heads * value_dim
        proj = jnp.matmul(x, inproj_weight.T, precision=projection)
        raw = proj[:, :2 * qk + vz]
        z = proj[:, 2 * qk + vz:2 * qk + 2 * vz].reshape(t, heads, value_dim)
        b = proj[:, 2 * qk + 2 * vz:2 * qk + 2 * vz + heads]
        a = proj[:, 2 * qk + 2 * vz + heads:]
        taps = conv_weight.shape[0]
        padded = jnp.pad(raw, ((taps - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[j:j + t] * conv_weight[j]
                              for j in range(taps)))
        q = _l2(qkv[:, :qk].reshape(t, heads, key_dim)) / key_dim ** 0.5
        k = _l2(qkv[:, qk:2 * qk].reshape(t, heads, key_dim))
        v = qkv[:, 2 * qk:].reshape(t, heads, value_dim)
        beta = beta_scale * jax.nn.sigmoid(b)
        alpha = jnp.exp(-jnp.exp(A_log) * jax.nn.softplus(a + dt_bias))

        def step(s, inp):  # one position: s (heads, value_dim, key_dim)
            q_t, k_t, v_t, beta_t, alpha_t = inp
            s = alpha_t[:, None, None] * s
            read = jnp.einsum("hvk,hk->hv", s, k_t)
            s = s + (beta_t[:, None] * (v_t - read))[:, :, None] \
                * k_t[:, None, :]
            return s, jnp.einsum("hvk,hk->hv", s, q_t)

        last, o = jax.lax.scan(
            step, jnp.zeros((heads, value_dim, key_dim), x.dtype),
            (q, k, v, beta, alpha))
        y = _gated_norm(o, z, gnorm_gamma, eps).reshape(t, vz)
        x = x + _rms(y @ outproj_weight.T, ln1_gamma, eps)
        return (_mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps),
                padded[t:], last)


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _attention_layer(x, ln1_gamma, qkv_weight, qnorm_gamma, knorm_gamma,
                     out_weight, ln2_gamma, ffn1_weight, ffn2_weight, heads,
                     eps):
    with jax.default_matmul_precision("highest"):
        t, d = x.shape
        dh = d // heads
        q, k, v = jnp.split(x @ qkv_weight.T, 3, axis=-1)
        q, k = _rms(q, qnorm_gamma, eps), _rms(k, knorm_gamma, eps)
        q, k, v = (part.reshape(t, heads, dh).transpose(1, 0, 2)
                   for part in (q, k, v))
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / dh ** 0.5
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + _rms(ctx.transpose(1, 0, 2).reshape(t, d) @ out_weight.T,
                     ln1_gamma, eps)
        return _mlp(x, ln2_gamma, ffn1_weight, ffn2_weight, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def _linear_sizes(config):
    # as many value heads as key heads: one state a head
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    return dict(heads=config["linear_num_value_heads"],
                key_dim=config["linear_key_head_dim"],
                value_dim=config["linear_value_head_dim"],
                beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0,
                eps=float(config["rms_norm_eps"]))


def _embed(params, tokens):
    return params["embed_weight"][jnp.asarray(tokens, jnp.int32)]


def first_mixer_state(params, config, tokens):
    """What layer 0, a linear-attention layer, keeps after `tokens`:
    ``(conv window (taps - 1, channels), state (heads, value_dim,
    key_dim))``, with the layer's INPUT PROJECTION multiplied at the
    device's default precision — as the configuration states the model
    under test multiplies its projections (one bfloat16 pass on a TPU,
    float32 on a CPU) — and everything after it float32 at "highest",
    position by position.  Layer 0's input is the embedding itself (this
    block norms no input), so nothing else of the model under test's
    arithmetic comes before this state: what is left between its layer-0
    state and this one is the conv and the delta rule alone."""
    assert config["layer_types"][0] == "linear_attention"
    _, window, state = _linear_layer(
        _embed(params, tokens), *(params["l0_" + n] for n in LINEAR_PARAMS),
        projection="default", **_linear_sizes(config))
    return window, state


def logits(params, config, tokens, last=None):
    """One sequence: logits ``(T, vocab)`` at every position, or at the
    last `last` positions only (a long context's head is 0.4 MB a row)."""
    x = _embed(params, tokens)
    eps = float(config["rms_norm_eps"])
    for i, kind in enumerate(config["layer_types"]):
        if kind == "linear_attention":
            x, _, _ = _linear_layer(
                x, *(params["l%d_%s" % (i, n)] for n in LINEAR_PARAMS),
                **_linear_sizes(config))
        else:
            x = _attention_layer(
                x, *(params["l%d_%s" % (i, n)] for n in ATTENTION_PARAMS),
                heads=config["num_attention_heads"], eps=eps)
    if last is not None:
        x = x[-int(last):]
    return _head(x, params["ln_f_gamma"], params["head_weight"], eps)
