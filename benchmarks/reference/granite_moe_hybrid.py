"""Plain reference for the `granite_moe_hybrid` family: the Granite
4.0-H decoder WITH routed experts (`ibm-granite/granite-4.0-h-small`
config.json, `model_type: granitemoehybrid`, 32B-A9B; equations as
`modeling_granitemoehybrid.py` of the transformers library has them) as
one full forward pass in straightforward float32 `jax.numpy` at "highest"
matmul precision — no cache, no batching, no chunking, no kernels.
Independent of `mxnet_tpu`: only the parameter names and layouts follow
the model under test.

    h = embedding_multiplier * embed[tok]
    per layer:  a  = h + residual_multiplier * Mixer(RMS(h; g1))
                u  = RMS(a; g2)
                h' = a + residual_multiplier * (Routed(u) + Shared(u))
    logits = RMS(h_L; g_f) embed^T / logits_scaling

The two mixers are `reference/granite_hybrid.py`'s, IMPORTED: that file's
layer functions end in its dense MLP, which adds ``residual * W_out
(silu(a) * b)``; handed an MLP of width one whose matrices are zero
(`_NO_MLP`) they add an exact 0 and return ``h + residual * Mixer(RMS(h;
g1))`` — the mixer half of a layer and nothing else (Mamba-2: the
recurrence a `lax.scan` over positions, 128 heads x 64, one group of 128
states, conv of 4 taps with bias, the gate before the norm over all 8,192
channels; attention: a full causal softmax, 32 query heads over 8 K/V
heads, no position signal, scores x attention_multiplier).

The expert layer, as published (`GraniteMoeHybridMoE` +
`GraniteMoeHybridMLP`):

    logits = W_r u                      (E = 72 wide, no bias, float32)
    top    = the experts_per_token (10) largest LOGITS
    w      = softmax over those ten logits alone
    Routed(u) = sum_{e in top} w_e W_out,e (silu(a_e) * b_e),
                [a_e | b_e] = W_in,e u
    Shared(u) = W_out (silu(a) * b),  [a | b] = W_in u       every token,
                                                    no gate of its own

A CHIP'S SHARE (`held_experts` ``(first, count)``; None = the whole
layer): the router, the choice and the softmax stay E wide and keep ten;
only the terms of the experts ``first .. first + count`` are summed, and
what the others would add is left out — neither computed nor stood in
for.  The shared MLP and both mixers are computed whole.  A sliced
vocabulary is a smaller one.

Departures from the published code, none in the mathematics: expert
matrices are stacked by kind, ``gate (E', d, f)`` / ``up (E', d, f)`` /
``down (E', f, d)`` (the checkpoint fuses gate and up into one ``(E, 2 f,
d)`` input matrix) and the shared MLP's likewise ``(d, s)`` / ``(d, s)``
/ ``(s, d)``; each held expert is applied to every position with its
weight (0 where it was not chosen).  Not run: the router's auxiliary
loss, dropout.
"""
import functools

import jax
import jax.numpy as jnp

from . import granite_hybrid as base

ROUTED = ("ln2_gamma", "router_weight", "gate_weight", "up_weight",
          "down_weight", "shared_gate_weight", "shared_up_weight",
          "shared_down_weight")
MAMBA = base.MAMBA_PARAMS[:-3]          # the mixer's own, ln1 first
ATTENTION = base.ATTENTION_PARAMS[:-3]


def _no_mlp(d, dtype):
    """An MLP of width one that adds an exact zero: ``(ln2_gamma,
    ffn1_weight (2, d), ffn2_weight (d, 1))``."""
    return (jnp.ones((d,), dtype), jnp.zeros((2, d), dtype),
            jnp.zeros((d, 1), dtype))


def mamba_mixer(x, weights, config, projection=None):
    """``(x + residual * Mamba2(RMS(x; g1)), conv window, state)`` for
    ``x (T, d)``: `granite_hybrid._mamba_layer` with no MLP behind it."""
    return base._mamba_layer(x, *weights, *_no_mlp(x.shape[1], x.dtype),
                             projection=projection,
                             **base._mamba_sizes(config))


def attention_mixer(x, weights, config):
    """``x + residual * Attention(RMS(x; g1))``."""
    return base._attention_layer(
        x, *weights, *_no_mlp(x.shape[1], x.dtype),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        scale=float(config["attention_multiplier"]),
        eps=float(config["rms_norm_eps"]),
        residual=float(config["residual_multiplier"]))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router_weight, top_k, first=0, count=None):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,)).  The published form: the `top_k` largest logits, a
    softmax over them alone.  The margin is how far the choice among the
    experts `first` .. `first + count` (default all) lies from changing,
    as `reference/mistral4.py` measures it: over a softmax of ALL logits
    (same order as the logits), the least distance of one of THEIR
    probabilities from the edge of the choice — the first left out for an
    expert that is chosen, the last chosen for one that is not — as a
    share of the last chosen probability; for two neighbours a logit gap
    of g reads ``1 - exp(-g)``.  A tie between two experts outside the
    range moves no term of the range's sum, only the softmax's divisor."""
    logits = x.astype(jnp.float32) @ router_weight
    ranked = jnp.argsort(-logits, axis=-1)
    rows = jnp.arange(x.shape[0])[:, None]
    kept = jnp.take_along_axis(logits, ranked[:, :top_k], axis=-1)
    weights = jnp.zeros_like(logits).at[rows, ranked[:, :top_k]].set(
        jax.nn.softmax(kept, axis=-1))
    probs = jax.nn.softmax(logits, axis=-1)
    best = jnp.take_along_axis(probs, ranked[:, :top_k + 1], axis=-1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    from_edge = jnp.where(weights > 0, probs - first_out, last_in - probs)
    mine = slice(first, None if count is None else first + count)
    return weights, (from_edge[:, mine] / last_in).min(axis=-1)


def expert_layer(x, router_weight, gate_weight, up_weight, down_weight,
                 shared, top_k, first):
    """``Routed(x) + Shared(x)`` for normed input `x (T, d)`: the routed
    sum over the experts whose matrices are given — experts `first` ..
    `first + count` of the router's E — and, where `shared` ``(gate, up,
    down)`` is not None, the shared MLP.  Returns (y, margin)."""
    count = gate_weight.shape[0]
    weights, margin = route(x, router_weight, top_k, first, count)
    # (the weights are float32; the sum runs in the dtype of `x`)
    mine = weights[:, first:first + count].astype(x.dtype)

    def one(y, expert):       # every position through one expert, weighted
        gate, up, down, w = expert
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (gate_weight, up_weight, down_weight, mine.T))
    if shared is not None:
        y = y + _swiglu(x, *shared)
    return y, margin


@functools.partial(jax.jit, static_argnames=("top_k", "first", "eps",
                                             "residual"))
def routed_block(x, ln2_gamma, router_weight, gate_weight, up_weight,
                 down_weight, shared_gate_weight, shared_up_weight,
                 shared_down_weight, top_k, first, eps, residual):
    """``x + residual * (Routed(u) + Shared(u))``, ``u = RMS(x; g2)``."""
    with jax.default_matmul_precision("highest"):
        y, margin = expert_layer(
            base._rms(x, ln2_gamma, eps), router_weight, gate_weight,
            up_weight, down_weight,
            (shared_gate_weight, shared_up_weight, shared_down_weight),
            top_k, first)
        return x + residual * y, margin


def first_mixer_state(params, config, tokens):
    """What layer 0, a Mamba layer, keeps after `tokens`: ``(conv window,
    state)`` with the layer's input projection at the device's default
    precision and everything after it float32 at "highest"
    (`granite_hybrid.first_mixer_state` says why), with no MLP behind
    the mixer to compute."""
    assert config["layer_types"][0] == "mamba", config["layer_types"][0]
    _, window, state = mamba_mixer(
        base._embed(params, config, tokens),
        [params["l0_" + n] for n in MAMBA], config, projection="default")
    return window, state


def forward(params, config, tokens, rows=None, dtype=None, held="config"):
    """One sequence: (logits at the positions `rows` (default all) over
    the vocabulary `params` holds, margins (layers, T)).  `held`
    ``(first, count)``: the experts whose matrices `params` holds
    (default: the configuration's `held_experts`; None: every expert).
    `dtype`: THE CONTROL — every weight cast to it as it is used, so that
    activations and state are of it too (the router's product stays
    float32 of the cast operands) — which the family's check has to
    refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    if held == "config":
        held = config.get("held_experts")
    first = 0 if held is None else int(held[0])
    eps = float(config["rms_norm_eps"])
    x = (float(config["embedding_multiplier"])
         * cast(params["embed_weight"])[jnp.asarray(tokens, jnp.int32)])
    margins = []
    for i, kind in enumerate(config["layer_types"]):
        layer = lambda names: [cast(params["l%d_%s" % (i, n)])  # noqa: E731
                               for n in names]
        if kind == "mamba":
            x, _, _ = mamba_mixer(x, layer(MAMBA), config)
        else:
            x = attention_mixer(x, layer(ATTENTION), config)
        x, margin = routed_block(
            x, *layer(ROUTED), top_k=config["num_experts_per_tok"],
            first=first, eps=eps,
            residual=float(config["residual_multiplier"]))
        margins.append(margin)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return (base._head(x, cast(params["ln_f_gamma"]),
                       cast(params["embed_weight"]), eps,
                       float(config["logits_scaling"])),
            jnp.stack(margins))


def logits(params, config, tokens, held="config"):
    return forward(params, config, tokens, held=held)[0]
