"""Plain reference for the `smallthinker` family: PowerInfer's
SmallThinker decoder (`PowerInfer/SmallThinker-21BA3B-Instruct`
config.json; Song et al., arXiv:2507.20984) as one full forward pass in
straightforward float32 `jax.numpy` at "highest" matmul precision — no
cache, no ring, no kernel, no batching.  Independent of `mxnet_tpu`: only
the parameter names and layouts follow the model under test.

With `d` the hidden size, `H` query heads over `H_kv` K/V heads of `d_h`,
`W` the window, `E` experts of which a token keeps `k`, block i on the
stream `h`:

    x  = RMS_in(h)                                  a gain, eps 1e-6
    z  = x W_r                                      (E,), float32: the router
                                                    reads what the attention
                                                    reads, BEFORE it runs
    a  = h + Attn_i(x)
    y  = RMS_post(a)
    p  = softmax(z) over all E;  S = top-k of p
    h' = a + sum_{e in S} (p_e / sum_{e' in S} p_e') E_e(y)
    E_e(y) = (relu(y Wg_e) * (y Wu_e)) Wd_e          "sparse ReGLU"
    Attn_i(x): q = x Wq -> (H, d_h);  k = x Wk, v = x Wv -> (H_kv, d_h)
        sliding_window_layout[i] == 1:  q, k = rotary(q, k)  rotate-half,
            angle position * theta^(-j / (d_h / 2));  row t attends s <= t
            with t - s < W
        sliding_window_layout[i] == 0:  NO position signal (rope_layout[i]
            == 0); row t attends every s <= t
        s_ts = q_t . k_s / sqrt(d_h);  o = softmax_s(s) v;  out = o Wo
        (a K/V head serves H / H_kv consecutive query heads)
    logits = RMS_f(h_L) W_head                       untied, embedding unscaled

Departures from the published code: Q, K and V are ONE fused projection
``[q | k | v]``; expert matrices are stacked ``(E, d, ff)`` / ``(E, ff,
d)`` and each expert is applied to every position with its weight (0 where
it was not chosen) instead of gathering tokens — the same sums in another
order; attention is computed in blocks of query positions and the expert
layer in blocks of rows, so that ten thousand positions at the published
widths fit beside a serving tenant; no dropout, no auxiliary loss.

`forward`'s two controls serve the comparison's limits and nothing else:
`dtype` — every weight but the router's cast to it as it is used and the
stream carried in it (the nearest precision below the stated one) — and
`fault`, ONE of `FAULTS`: the mathematics a wrong program would compute.
"""
import functools

import jax
import jax.numpy as jnp

LAYER = ("ln1_gamma", "qkv_weight", "out_weight", "ln2_gamma",
         "router_weight", "gate_weight", "up_weight", "down_weight")
QUERY_BLOCK = 256
ROW_BLOCK = 4096
# what a wrong program would compute, one at a time (`forward(fault=...)`)
FAULTS = ("router_on_ffn_input", "silu_gate", "no_window", "rope_on_full",
          "not_renormalised")


def _rms(x, gamma, eps):
    f = x.astype(jnp.float32)
    f = f / jnp.sqrt(jnp.mean(jnp.square(f), axis=-1, keepdims=True) + eps)
    return (f * gamma).astype(x.dtype)


def _rotary(x, theta):
    """x (heads, T, d_head): rotate-half over the whole head, row t at
    position t."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + turned * sin).astype(x.dtype)


def attention(x, qkv_weight, out_weight, heads, kv_heads, d_head, window,
              rotary, theta):
    """``Attn(x)`` of normed `x (T, d)``: `window` 0 attends every earlier
    position, `window` W the last W; `rotary` turns Q and K."""
    t = x.shape[0]
    qw, kw = heads * d_head, kv_heads * d_head
    q, k, v = jnp.split(x @ qkv_weight.T, (qw, qw + kw), axis=-1)
    q = q.reshape(t, heads, d_head).transpose(1, 0, 2)
    k = k.reshape(t, kv_heads, d_head).transpose(1, 0, 2)
    v = v.reshape(t, kv_heads, d_head).transpose(1, 0, 2)
    if rotary:
        q, k = _rotary(q, theta), _rotary(k, theta)
    # each K/V head repeated for its group of query heads
    k, v = (jnp.repeat(part, heads // kv_heads, axis=0) for part in (k, v))
    # blocks of query positions, one after the other; the last block's
    # rows past T are nobody's
    blocks = -(-t // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - t), (0, 0)))

    def one(start):
        i = start + jnp.arange(QUERY_BLOCK)[:, None]
        j = jnp.arange(t)[None, :]
        keep = j <= i
        if window:
            keep &= i - j < window
        s = jnp.einsum(
            "hqd,hkd->hqk",
            jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=1),
            k).astype(jnp.float32) / jnp.sqrt(float(d_head))
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v)

    o = jax.lax.map(one, jnp.arange(blocks) * QUERY_BLOCK)
    o = o.transpose(0, 2, 1, 3).reshape(blocks * QUERY_BLOCK, qw)[:t]
    return o @ out_weight.T


def route(x, router_weight, top_k, renormalise=True):
    """(weights (T, E) — the kept experts' share of the sum, 0 elsewhere —,
    margin (T,): how far the last kept probability lies above the first
    one left out, as a share of the last kept).  float32 at "highest"
    whatever `x` is carried in."""
    logits = jnp.dot(x.astype(jnp.float32), router_weight,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    ranked = jnp.argsort(-probs, axis=-1)
    best = jnp.take_along_axis(probs, ranked[:, :top_k + 1], axis=-1)
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ranked[:, :top_k]].set(1.0)
    weights = probs * chosen
    if renormalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1]


def experts(y, weights, gate_weight, up_weight, down_weight, act):
    """``sum_e weights[:, e] * E_e(y)`` of normed `y (T, d)``, a block of
    rows at a time, every position through one expert after the other."""
    out = []
    for start in range(0, y.shape[0], ROW_BLOCK):      # blocks of rows
        rows = y[start:start + ROW_BLOCK]

        def one(acc, expert, rows=rows):
            gate, up, down, w = expert
            term = (act(rows @ gate) * (rows @ up)) @ down
            return acc + w[:, None].astype(rows.dtype) * term, None

        acc, _ = jax.lax.scan(
            one, jnp.zeros_like(rows),
            (gate_weight, up_weight, down_weight,
             weights[start:start + ROW_BLOCK].T))
        out.append(acc)
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "d_head", "window", "rotary", "theta", "top_k",
    "eps", "fault"))
def block(h, ln1_gamma, qkv_weight, out_weight, ln2_gamma, router_weight,
          gate_weight, up_weight, down_weight, heads, kv_heads, d_head,
          window, rotary, theta, top_k, eps, fault=None):
    """One block on the stream `h (T, d)`: (h', the router's margin)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, ln1_gamma, eps)
        a = h + attention(x, qkv_weight, out_weight, heads, kv_heads,
                          d_head, window, rotary, theta)
        y = _rms(a, ln2_gamma, eps)
        weights, margin = route(
            y if fault == "router_on_ffn_input" else x, router_weight,
            top_k, renormalise=fault != "not_renormalised")
        act = jax.nn.silu if fault == "silu_gate" else jax.nn.relu
        return a + experts(y, weights, gate_weight, up_weight, down_weight,
                           act), margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return (_rms(x, gamma, eps) @ head.T).astype(jnp.float32)


def forward(params, config, tokens, rows=None, dtype=None, fault=None):
    """One sequence: (logits at the positions `rows` (default all),
    margins (layers, T)).  `dtype` and `fault`: the controls (module
    docstring)."""
    assert fault is None or fault in FAULTS, fault
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    x = cast(params["embed_weight"])[jnp.asarray(tokens, jnp.int32)]
    margins = []
    for i in range(config["num_hidden_layers"]):
        sliding = bool(config["sliding_window_layout"][i])
        window = config["sliding_window_size"] if sliding else 0
        weights = [params["l%d_%s" % (i, n)] if n == "router_weight"
                   else cast(params["l%d_%s" % (i, n)]) for n in LAYER]
        x, margin = block(
            x, *weights, heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            d_head=config["head_dim"],
            window=0 if fault == "no_window" else window,
            rotary=bool(config["rope_layout"][i]) or fault == "rope_on_full",
            theta=float(config["rope_theta"]),
            top_k=config["moe_num_active_primary_experts"],
            eps=float(config["rms_norm_eps"]), fault=fault)
        margins.append(margin)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return (_head(x, cast(params["ln_f_gamma"]), cast(params["head_weight"]),
                  float(config["rms_norm_eps"])), jnp.stack(margins))


def logits(params, config, tokens):
    return forward(params, config, tokens)[0]
