"""Plain reference for the `opt` family: the OPT decoder (Zhang et al.,
arXiv:2205.01068; `facebook/opt-*` config.json) as one full forward pass
in straightforward float32 `jax.numpy` at "highest" matmul precision —
no cache, no batching, no kernels.  Pre-LayerNorm blocks, learned
positions, ReLU feed-forward, biased projections, final LayerNorm, head
tied to the token embedding.  Independent of `mxnet_tpu`: only the
parameter names follow the model under test.

Departures from the published model, as the model under test has them
(the configuration file lists them under `assumed`): positions index the
table from 0 (OPT offsets them by 2); Q, K and V are one fused
projection whose output splits into thirds.
"""
import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
LAYER_PARAMS = ("ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias",
                "out_weight", "out_bias", "ln2_gamma", "ln2_beta",
                "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias")


def _ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gamma + beta


# one program for all layers: jitted once, called per layer with that
# layer's weights, so the reference compiles in seconds at any depth
@functools.partial(jax.jit, static_argnames=("heads",))
def _layer(h, ln1_gamma, ln1_beta, qkv_weight, qkv_bias, out_weight,
           out_bias, ln2_gamma, ln2_beta, ffn1_weight, ffn1_bias,
           ffn2_weight, ffn2_bias, heads):
    with jax.default_matmul_precision("highest"):
        t, d = h.shape
        dh = d // heads
        x = _ln(h, ln1_gamma, ln1_beta)
        qkv = x @ qkv_weight.T + qkv_bias
        q, k, v = (part.reshape(t, heads, dh).transpose(1, 0, 2)
                   for part in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(float(dh))
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
        h = h + ctx.transpose(1, 0, 2).reshape(t, d) @ out_weight.T + out_bias
        x = _ln(h, ln2_gamma, ln2_beta)
        f = jax.nn.relu(x @ ffn1_weight.T + ffn1_bias)
        return h + f @ ffn2_weight.T + ffn2_bias


@jax.jit
def _head(h, gamma, beta, embed):
    with jax.default_matmul_precision("highest"):
        return _ln(h, gamma, beta) @ embed.T


def logits(params, config, tokens):
    """Next-token logits (T, vocab) at every position of one sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params["embed_weight"][tokens] + params["pos_weight"][:len(tokens)]
    for i in range(config["num_hidden_layers"]):
        h = _layer(h, *(params["l%d_%s" % (i, n)] for n in LAYER_PARAMS),
                   heads=config["num_attention_heads"])
    return _head(h, params["ln_f_gamma"], params["ln_f_beta"],
                 params["embed_weight"])
