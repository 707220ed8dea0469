"""Plain reference for the `olmoe` family: the OLMoE decoder (Muennighoff
et al., arXiv:2409.02060; `allenai/OLMoE-1B-7B-*` config.json) as one
full forward pass in straightforward float32 `jax.numpy` at "highest"
matmul precision — no cache, no batching, no kernels, and a Python loop
over each token's chosen experts.  Independent of `mxnet_tpu`: only the
parameter names and layouts follow the model under test.

Per layer, `x` the residual stream, every norm an RMSNorm with a gain:

    h = norm_in(x)
    q = norm_q(h Wq), k = norm_k(h Wk), v = h Wv     (norms over all d)
    q, k <- rotary(q), rotary(k)      (per head, rotate-half, position t)
    x += softmax(q k^T / sqrt(d_head) + causal) v Wo
    h = norm_post(x)
    p = softmax(h Wr)                 (over the experts, float32)
    x += sum_{e in top-k(p)} p_e Wdown_e (silu(Wgate_e h) * Wup_e h)

with `p_e` NOT renormalised over the chosen experts (`norm_topk_prob`
false), then `logits = norm_f(x) Whead`, the head its own matrix.

Layouts, as the model under test holds them (the configuration file
lists them under `assumed`): Q, K and V are one fused `(3d, d)` matrix
whose output splits into thirds; expert matrices are stacked
`gate/up (E, d, ff)`, `down (E, ff, d)` and the router is `(d, E)`.
"""
import functools

import jax
import jax.numpy as jnp

LAYER_PARAMS = ("ln1_gamma", "qkv_weight", "qnorm_gamma", "knorm_gamma",
                "out_weight", "ln2_gamma", "router_weight", "gate_weight",
                "up_weight", "down_weight")


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


# one program for all layers: jitted once, called per layer with that
# layer's weights, so the reference compiles in seconds at any depth
@functools.partial(jax.jit, static_argnames=("heads", "top_k", "eps",
                                             "theta"))
def _layer(x, ln1_gamma, qkv_weight, qnorm_gamma, knorm_gamma, out_weight,
           ln2_gamma, router_weight, gate_weight, up_weight, down_weight,
           heads, top_k, eps, theta):
    with jax.default_matmul_precision("highest"):
        t, d = x.shape
        dh = d // heads
        h = _rms(x, ln1_gamma, eps)
        q, k, v = jnp.split(h @ qkv_weight.T, 3, axis=-1)
        q, k = _rms(q, qnorm_gamma, eps), _rms(k, knorm_gamma, eps)
        q, k, v = (part.reshape(t, heads, dh).transpose(1, 0, 2)
                   for part in (q, k, v))
        q, k = _rotary(q, theta), _rotary(k, theta)
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(float(dh))
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + ctx.transpose(1, 0, 2).reshape(t, d) @ out_weight.T
        h = _rms(x, ln2_gamma, eps)
        p = jax.nn.softmax(h @ router_weight, axis=-1)
        ranked = jnp.argsort(-p, axis=-1)
        best = jnp.take_along_axis(p, ranked[:, :top_k + 1], axis=-1)
        for j in range(top_k):  # each token's j-th expert, one at a time
            e = ranked[:, j]
            inner = (jax.nn.silu(jnp.einsum("td,tdf->tf", h, gate_weight[e]))
                     * jnp.einsum("td,tdf->tf", h, up_weight[e]))
            x = x + best[:, j:j + 1] * jnp.einsum("tf,tfd->td", inner,
                                                  down_weight[e])
        return x, best[:, top_k - 1] - best[:, top_k]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def forward(params, config, tokens):
    """One sequence: (logits (T, vocab) at every position, margins
    (layers, T) — how far the last chosen expert's router probability
    lies above the first one left out)."""
    x = params["embed_weight"][jnp.asarray(tokens, jnp.int32)]
    eps = float(config["rms_norm_eps"])
    margins = []
    for i in range(config["num_hidden_layers"]):
        x, margin = _layer(
            x, *(params["l%d_%s" % (i, n)] for n in LAYER_PARAMS),
            heads=config["num_attention_heads"],
            top_k=config["num_experts_per_tok"], eps=eps,
            theta=float(config["rope_theta"]))
        margins.append(margin)
    return (_head(x, params["ln_f_gamma"], params["head_weight"], eps),
            jnp.stack(margins))


def logits(params, config, tokens):
    return forward(params, config, tokens)[0]
