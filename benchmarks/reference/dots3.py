"""Plain reference for the `dots3` family: the language model of
`dots-studio/dots3-note-prev` (config.json, `model_type` `dots3_note`,
288B-A17B) as one full forward pass in straightforward float32
`jax.numpy` at "highest" matmul precision — no cache, no ring, no kernel,
no absorbed form, no gather: every head's K and V are made from the latent
rows, the learned selection is a MASK on causal attention, and the window
is a mask too.  Independent of `mxnet_tpu`: only the parameter names
follow the model under test.

With `d` the hidden size, eps `rms_norm_eps`, no bias anywhere, and per
layer `x = RMS_in(h)`, `a = h + Mix(x)`, `h' = a + FFN(RMS_post(a))`:

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g              a PLAIN gain

**Latent attention** (both layer kinds, each with ITS OWN sizes: H heads,
query rank r_q, key/value rank r_kv, n unturned and r rotary query
channels, value width v, rotary base theta):

    c_q = RMS_qa(x W_qa) * s_q,       s_q  = sqrt(d / r_q)     `lora_rescale`
    q   = c_q W_qb        -> H heads of [q_n (n) | q_r (r)]
    [c_kv (r_kv) | k_r (r)] = x W_kva
    c   = RMS_kva(c_kv) * s_kv,       s_kv = sqrt(d / r_kv)
    [k_n,h (n) | v_h (v)] = c W_kvb,h                        per head h
    rotary (rotate-half pairs (j, j + r/2), angle p * theta^(-2j/r)) on
        q_r of each head and on the ONE k_r all heads share
    score_h[t, s] = (q_n,h[t] . k_n,h[s] + q_r,h[t] . k_r[s]) * (n + r)^-1/2
    ctx_h[t] = softmax over s in S_t of score_h[t, s], times v_h[s]
    g = sigmoid(x W_g)                      H scalars: the HEADWISE gate
    Mix(x) = concat_h(g_h * ctx_h) W_o

**Full layer**: S_t is the indexer's choice.  With `index_n_heads` J heads
of `index_head_dim` D and `index_topk` K:

    q_I = c_q W_Iq  (J x D);   k_I = LayerNorm(x W_Ik)  (ONE D-wide key)
    rotary (the layer's theta, rotate-half) on the first r channels of
        every q_I head and of k_I
    w   = x W_Iw * J^-1/2 * D^-1/2
    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])
    S_t = the K largest I[t, s] over s <= t   (all of them while t < K)

**Sliding layer**: S_t = {s <= t : t - s < W}, W `sliding_window_size`:
the row itself and the W - 1 before it.

**FFN**: layer 0 (`first_k_dense_replace` 1) a dense SwiGLU
``(silu(x A) * (x B)) C`` of `intermediate_size`; every later layer E
routed experts of `moe_intermediate_size`, k a token, and one ungated
shared expert:

    p = sigmoid(x W_r)                         float32
    S = the k largest of p + b                 b the selection bias
    w_e = p_e / sum_{e' in S} p_e' * routed_scaling_factor
    MoE(x) = sum_{e in S} w_e Expert_e(x) + Shared(x)

    logits = RMS_f(h_L) W_head                 untied

**Readings the config leaves open** are the configuration's `assumed`
(the rescale's form, the gate's input and place, the indexer's form, the
window's count, the softmax scales, the layouts); the mathematics above is
what they say.

**The checkpoint's layout.**  `params` hold `l<i>_qb_weight` head by head,
``[q_n,h | q_r,h]``; the model under test keeps all heads' q_n, then all
heads' q_r (`families/dots3.py checkpoint_layout` maps its parameters to
these; a test ties the two).  Rotary channels are in rotate-half order on
both sides.

**One chip's share.**  `held` ``(first, count)`` — by default the
configuration's `held_experts` — says which experts' matrices `params`
holds: the choice S and the weights stay over all E, the terms of experts
outside the range are left out, the shared expert is computed whole.  A
sliced vocabulary is a smaller one.

Departures from the published code, none in the mathematics: attention is
computed a group of `HEAD_GROUP` heads and a block of `QUERY_BLOCK` query
positions at a time, against masks ``(T, T)`` made once a layer, so that
fifteen thousand positions fit beside a serving tenant; expert matrices
are stacked and each held expert is applied to every position with its
weight (0 where it was not chosen).  Not run: the vision tower, the audio
encoder, the MTP module, the indexer's Hadamard rotation and fp8 store (an
orthogonal rotation of q_I and k_I alike changes no product).
"""
import functools

import jax
import jax.numpy as jnp

MIXER = ("ln1_gamma", "qa_weight", "qa_norm_gamma", "qb_weight",
         "kva_weight", "kva_norm_gamma", "kvb_weight", "out_weight",
         "hgate_weight")
INDEXER = ("iq_weight", "ik_weight", "ik_norm_gamma", "ik_norm_beta",
           "iw_weight")
DENSE = ("ln2_gamma", "ffn1_weight", "ffn2_weight")
ROUTED = ("ln2_gamma", "router_weight", "router_bias", "gate_weight",
          "up_weight", "down_weight", "shared_gate_weight",
          "shared_up_weight", "shared_down_weight")
QUERY_BLOCK = 128
HEAD_GROUP = 16
INDEX_BLOCK = 64


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def _layer_norm(x, gain, shift, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + shift


def _rotary(x, theta):
    """``x (..., T, r)`` in rotate-half order, row t at position t."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def geometry(config, kind):
    """The latent attention's sizes of one layer kind, ``"full_attention"``
    or ``"sliding_attention"``, as a dict: heads, q_rank, kv_rank, nope,
    rope, value, theta."""
    p = "" if kind == "full_attention" else "swa_"
    return dict(heads=config[p + "num_attention_heads"],
                q_rank=config[p + "q_lora_rank"],
                kv_rank=config[p + "kv_lora_rank"],
                nope=config[p + "qk_nope_head_dim"],
                rope=config[p + "qk_rope_head_dim"],
                value=config[p + "v_head_dim"],
                theta=float(config[p + "rope_theta"]))


@functools.partial(jax.jit, static_argnames=("rope", "theta", "rescale",
                                             "eps"))
def _latents(x, ln1_gamma, qa_weight, qa_norm_gamma, kva_weight,
             kva_norm_gamma, rope, theta, rescale, eps):
    """(normed stream, c_q, c, k_r) of ``x (T, d)``."""
    with jax.default_matmul_precision("highest"):
        d = x.shape[-1]
        normed = _rms(x, ln1_gamma, eps)
        c_q = _rms(normed @ qa_weight.T, qa_norm_gamma, eps)
        kva = normed @ kva_weight.T
        rank = kva.shape[-1] - rope
        c = _rms(kva[:, :rank], kva_norm_gamma, eps)
        if rescale:
            c_q = c_q * jnp.asarray((d / c_q.shape[-1]) ** 0.5, x.dtype)
            c = c * jnp.asarray((d / rank) ** 0.5, x.dtype)
        return normed, c_q, c, _rotary(kva[:, rank:], theta)


@functools.partial(jax.jit, static_argnames=("heads", "dim", "rope", "theta",
                                             "top_k", "eps"))
def selection(normed, c_q, iq_weight, ik_weight, ik_norm_gamma, ik_norm_beta,
              iw_weight, heads, dim, rope, theta, top_k, eps, keys=None):
    """``(keep (T, T) bool, scores' margin (T,), the keys (T, D))`` of the
    indexer: row t keeps s iff ``I[t, s]`` is among its `top_k` largest
    over ``s <= t``; the margin is the gap between the last kept and the
    first left-out score as a share of the kept scores' spread (inf while
    t < top_k).  `keys` ``(T, D)``, rotated, stand in for the indexer's
    own (the family's check hands it the program's cached ones to count
    how far the two choices overlap)."""
    with jax.default_matmul_precision("highest"):
        t = normed.shape[0]
        q = (c_q @ iq_weight.T).reshape(t, heads, dim)
        q = jnp.concatenate(
            [_rotary(q[..., :rope].transpose(1, 0, 2), theta).transpose(
                1, 0, 2), q[..., rope:]], axis=-1)
        k = _layer_norm(normed @ ik_weight.T, ik_norm_gamma, ik_norm_beta,
                        eps)
        k = jnp.concatenate([_rotary(k[:, :rope], theta), k[:, rope:]],
                            axis=-1)
        if keys is not None:
            k = keys.astype(k.dtype)
        w = (normed @ iw_weight.T) * jnp.asarray(
            heads ** -0.5 * dim ** -0.5, normed.dtype)
        kept = min(top_k, t)

        def block(args):
            q_b, w_b, first = args
            s = jax.nn.relu(jnp.einsum("qhd,kd->qhk", q_b, k))
            score = jnp.einsum("qh,qhk->qk", w_b, s).astype(jnp.float32)
            rows = first + jnp.arange(q_b.shape[0])[:, None]
            causal = jnp.arange(t)[None, :] <= rows
            score = jnp.where(causal, score, -jnp.inf)
            best = jax.lax.top_k(score, min(kept + 1, t))[0]
            edge = best[:, kept - 1:kept]
            out = best[:, kept] if kept < t else jnp.full((q_b.shape[0],),
                                                          -jnp.inf)
            margin = (edge[:, 0] - out) / (best[:, 0] - edge[:, 0] + 1e-30)
            return (score >= edge) & causal, margin

        blocks = t // INDEX_BLOCK
        keep, margin = jax.lax.map(block, (
            q.reshape(blocks, INDEX_BLOCK, heads, dim),
            w.reshape(blocks, INDEX_BLOCK, heads),
            jnp.arange(blocks) * INDEX_BLOCK))
        return keep.reshape(t, t), margin.reshape(t), k


def window_mask(t, window):
    """``(T, T)`` bool: row t keeps ``s <= t`` with ``t - s < window``."""
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (gap >= 0) & (gap < window)


@functools.partial(jax.jit, static_argnames=("nope", "rope", "value",
                                             "theta"))
def _group_attention(c_q, c, k_r, gate, keep, qb_weight, kvb_weight,
                     out_weight, nope, rope, value, theta):
    """What a group of G heads adds to the mixer's output: ``c_q (T,
    r_q)``, ``c (T, r_kv)``, ``k_r (T, r)`` rotated, ``gate (T, G)``, `keep`
    ``(T, T)``, the group's rows of `W_qb` ``(G, n + r, r_q)``, of `W_kvb`
    ``(G, n + v, r_kv)`` and columns of `W_o` ``(d, G, v)`` → ``(T, d)``."""
    with jax.default_matmul_precision("highest"):
        t = c_q.shape[0]
        q = jnp.einsum("tr,gfr->gtf", c_q, qb_weight)
        q_n, q_r = q[..., :nope], _rotary(q[..., nope:], theta)
        kv = jnp.einsum("tr,gfr->gtf", c, kvb_weight)
        k_n, v = kv[..., :nope], kv[..., nope:]
        scale = jnp.asarray((nope + rope) ** -0.5, c_q.dtype)

        def block(args):
            qn_b, qr_b, keep_b = args
            s = (jnp.einsum("gqd,gkd->gqk", qn_b, k_n)
                 + jnp.einsum("gqd,kd->gqk", qr_b, k_r)) * scale
            s = jnp.where(keep_b[None], s.astype(jnp.float32), -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("gqk,gkd->gqd", p, v)

        blocks = t // QUERY_BLOCK
        groups = q.shape[0]
        ctx = jax.lax.map(block, (
            q_n.reshape(groups, blocks, QUERY_BLOCK, nope).swapaxes(0, 1),
            q_r.reshape(groups, blocks, QUERY_BLOCK, rope).swapaxes(0, 1),
            keep.reshape(blocks, QUERY_BLOCK, t)))
        ctx = ctx.transpose(1, 0, 2, 3).reshape(groups, t, value)
        ctx = ctx * gate.T[:, :, None]
        return jnp.einsum("gtv,dgv->td", ctx, out_weight)


def latent_mixer(x, p, geo, keep_of, eps, rescale=True, head_gate=True):
    """``x + Mix(RMS_in(x))`` of ``x (T, d)`` for the mixer parameters `p`
    (by the names of `MIXER`) and the sizes `geo`; ``keep_of(normed,
    c_q)`` gives the layer's ``(T, T)`` mask.  Returns (the stream, the
    cached row ``[c | k_r] (T, r_kv + r)``)."""
    h, nope, rope, value = (geo[k] for k in ("heads", "nope", "rope",
                                             "value"))
    normed, c_q, c, k_r = _latents(
        x, p["ln1_gamma"], p["qa_weight"], p["qa_norm_gamma"],
        p["kva_weight"], p["kva_norm_gamma"], rope=rope, theta=geo["theta"],
        rescale=rescale, eps=eps)
    keep = keep_of(normed, c_q)
    with jax.default_matmul_precision("highest"):
        gate = (jax.nn.sigmoid(normed @ p["hgate_weight"].T) if head_gate
                else jnp.ones((x.shape[0], h), x.dtype))
    qb = p["qb_weight"].reshape(h, nope + rope, -1)
    kvb = p["kvb_weight"].reshape(h, nope + value, -1)
    out = p["out_weight"].reshape(-1, h, value)
    group = min(HEAD_GROUP, h)
    y = x
    for g in range(0, h, group):
        y = y + _group_attention(
            c_q, c, k_r, gate[:, g:g + group], keep, qb[g:g + group],
            kvb[g:g + group], out[:, g:g + group], nope=nope, rope=rope,
            value=value, theta=geo["theta"])
    return y, jnp.concatenate([c, k_r], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_block(x, ln2_gamma, ffn1_weight, ffn2_weight, eps):
    """``x + SwiGLU(RMS_post(x))``, ``ffn1`` the fused ``[A | B]``."""
    with jax.default_matmul_precision("highest"):
        normed = _rms(x, ln2_gamma, eps)
        a, b = jnp.split(normed @ ffn1_weight.T, 2, axis=-1)
        return x + (jax.nn.silu(a) * b) @ ffn2_weight.T


def route(x, router_weight, router_bias, top_k, norm_topk, scale, first=0,
          count=None):
    """(weights (T, E) — `w_e` for the chosen experts, 0 elsewhere —,
    margin (T,): the least distance of a score (with its bias) of the
    experts `first` .. `first + count` (default all) from the edge of the
    choice, as a share of the last chosen probability)."""
    probs = jax.nn.sigmoid(x.astype(jnp.float32)
                           @ router_weight.astype(jnp.float32))
    biased = probs + router_bias.astype(jnp.float32)
    ranked = jnp.argsort(-biased, axis=-1)
    best = jnp.take_along_axis(biased, ranked[:, :top_k + 1], axis=-1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ranked[:, :top_k]].set(1.0)
    weights = probs * chosen
    if norm_topk:
        weights = weights / weights.sum(-1, keepdims=True)
    from_edge = jnp.where(chosen > 0, biased - first_out, last_in - biased)
    mine = slice(first, None if count is None else first + count)
    last_prob = jnp.take_along_axis(probs, ranked[:, top_k - 1:top_k], -1)
    return weights * scale, (from_edge[:, mine] / last_prob).min(axis=-1)


def expert_layer(x, router_weight, router_bias, gate_weight, up_weight,
                 down_weight, shared, top_k, norm_topk, scale, first,
                 shared_times=1.0):
    """The expert layer's output for normed input `x (T, d)`: the routed
    sum over the experts whose matrices are given — experts `first` ..
    `first + count` of the router's E — plus `shared_times` (1: once) the
    shared expert ``(gate, up, down)``.  Returns (y, margin)."""
    count = gate_weight.shape[0]
    weights, margin = route(x, router_weight, router_bias, top_k, norm_topk,
                            scale, first, count)
    mine = weights[:, first:first + count].astype(x.dtype)

    def one(y, expert):       # every position through one expert, weighted
        gate, up, down, w = expert
        return y + w[:, None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (gate_weight, up_weight, down_weight, mine.T))
    return y + shared_times * _swiglu(x, *shared), margin


@functools.partial(jax.jit, static_argnames=(
    "top_k", "norm_topk", "scale", "first", "eps"))
def routed_block(x, ln2_gamma, router_weight, router_bias, gate_weight,
                 up_weight, down_weight, shared_gate_weight,
                 shared_up_weight, shared_down_weight, top_k, norm_topk,
                 scale, first, eps):
    """``x + MoE(RMS_post(x))``."""
    with jax.default_matmul_precision("highest"):
        y, margin = expert_layer(
            _rms(x, ln2_gamma, eps), router_weight, router_bias, gate_weight,
            up_weight, down_weight,
            (shared_gate_weight, shared_up_weight, shared_down_weight),
            top_k, norm_topk, scale, first)
        return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gamma, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gamma, eps) @ head.T


def forward(params, config, tokens, rows=None, dtype=None, held=None,
            keep_rows=(), index_keys=None):
    """One sequence: a dict of `logits` at the positions `rows` (default
    all) over the vocabulary `params` holds, the routers' `margins`
    ``(routed layers, T)``, the indexers' `index_margins` ``(full layers,
    T)``, each full layer's `keep` rows at the positions `keep_rows`
    ``(full layers, len(keep_rows), T)``, and the cached rows `latent` /
    `index` of every layer ``{layer: (T, width)}`` (a full layer's index
    keys under `index`).  `index_keys` ``{full layer: (T, D)}``: keys to
    make a SECOND choice from, whose rows at `keep_rows` come back under
    `keep_with` (the forward itself goes on with its own).  `held`
    ``(first, count)``: the experts `params` holds (default the
    configuration's `held_experts`).  `dtype`: THE
    CONTROL — every weight cast to it as it is used, so that activations
    are of it too — which the family's check has to refuse."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    tokens = [int(t) for t in tokens]
    true_len = len(tokens)
    # whole blocks: the pad's rows come after every real one and are cut
    block = max(QUERY_BLOCK, INDEX_BLOCK)
    tokens = tokens + [0] * (-true_len % block)
    t = len(tokens)
    x = cast(params["embed_weight"][jnp.asarray(tokens, jnp.int32)])
    eps = float(config["rms_norm_eps"])
    assumed = config["assumed"]
    rescale = bool(config["apply_mla_qkv_lora_rescale"])
    first = (held or config.get("held_experts") or (0, None))[0]
    out = {"margins": [], "index_margins": [], "keep": [], "keep_with": [],
           "latent": {}, "index": {}}
    at_rows = jnp.asarray(keep_rows, jnp.int32)
    for i, kind in enumerate(config["layer_types"]):
        p = {n: cast(params["l%d_%s" % (i, n)]) for n in MIXER}
        geo = geometry(config, kind)
        if kind == "full_attention":
            index = [cast(params["l%d_%s" % (i, n)]) for n in INDEXER]

            def keep_of(normed, c_q, i=i, index=index, geo=geo):
                sizes = dict(heads=config["index_n_heads"],
                             dim=config["index_head_dim"], rope=geo["rope"],
                             theta=geo["theta"], top_k=config["index_topk"],
                             eps=eps)
                keep, margin, keys = selection(normed, c_q, *index, **sizes)
                out["index_margins"].append(margin[:true_len])
                out["keep"].append(keep[at_rows, :true_len])
                out["index"][i] = keys[:true_len]
                if index_keys is not None:
                    theirs = jnp.zeros_like(keys).at[:true_len].set(
                        jnp.asarray(index_keys[i], keys.dtype))
                    out["keep_with"].append(selection(
                        normed, c_q, *index, keys=theirs,
                        **sizes)[0][at_rows, :true_len])
                return keep
        else:
            window = int(config["sliding_window_size"])
            assert assumed["window"]["counts_the_row_itself"]

            def keep_of(normed, c_q):
                return window_mask(t, window)
        x, latent = latent_mixer(x, p, geo, keep_of, eps, rescale=rescale)
        out["latent"][i] = latent[:true_len]
        if i < config["first_k_dense_replace"]:
            x = dense_block(x, *[cast(params["l%d_%s" % (i, n)])
                                 for n in DENSE], eps=eps)
        else:
            x, margin = routed_block(
                x, *[cast(params["l%d_%s" % (i, n)]) for n in ROUTED],
                top_k=config["num_experts_per_tok"],
                norm_topk=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"]), first=first,
                eps=eps)
            out["margins"].append(margin[:true_len])
    x = x[:true_len] if rows is None else x[jnp.asarray(rows, jnp.int32)]
    out["logits"] = _head(x, cast(params["ln_f_gamma"]),
                          cast(params["head_weight"]), eps)
    out["margins"] = jnp.stack(out["margins"])
    return out


def logits(params, config, tokens, held=None):
    return forward(params, config, tokens, held=held)["logits"]
