"""Plain reference for the `glm5` family: `zai-org/GLM-5` (config.json,
`model_type` `glm_moe_dsa`, 744B-A40B, 78 layers + 1 multi-token-prediction
layer) — the trunk, the MTP module and a token-at-a-time greedy decoder —
in straightforward float32 `jax.numpy` at "highest" matmul precision: no
cache, no ring, no kernel, no absorbed form, no gather, no draft, no
batching.  Every head's K and V are made from the latent rows and the
learned selection is a MASK on causal attention.  Independent of
`mxnet_tpu`: only the parameter names follow the model under test.

With `d` the hidden size (6,144), eps `rms_norm_eps`, no bias anywhere,
``RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g``, and per layer ``x =
RMS_in(h)``, ``a = h + Mix(x)``, ``h' = a + FFN(RMS_post(a))``:

**Latent attention** (H heads, query rank r_q, key/value rank r_kv, n
unturned and r rotary query channels, value width v, rotary base theta):

    c_q = RMS_qa(x W_qa)                        (r_q)
    q   = c_q W_qb        -> H heads of [q_n (n) | q_r (r)]
    [c_kv (r_kv) | k_r (r)] = x W_kva;    c = RMS_kva(c_kv)
    [k_n,h (n) | v_h (v)] = c W_kvb,h                        per head h
    rotary (INTERLEAVED pairs (2j, 2j + 1), angle p * theta^(-2j/r):
        `rope_interleave`) on q_r of each head and on the ONE k_r
    score_h[t, s] = (q_n,h[t] . k_n,h[s] + q_r,h[t] . k_r[s]) * (n + r)^-1/2
    ctx_h[t] = softmax over s in S_t of score_h[t, s], times v_h[s]
    Mix(x) = concat_h(ctx_h) W_o

**The indexer** (DeepSeek-V3.2's; J heads of D, the K best):

    q_I = c_q W_Iq  (J x D);   k_I = LayerNorm(x W_Ik)  (ONE D-wide key)
    rotary (interleaved: `indexer_rope_interleave`) on the first r
        channels of every q_I head and of k_I
    w   = x W_Iw * J^-1/2 * D^-1/2
    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])
    S_t = the K largest I[t, s] over s <= t   (all of them while t < K)

**FFN**: the first `first_k_dense_replace` layers a dense SwiGLU ``(silu(x
A) * (x B)) C`` of `intermediate_size`; every later layer E routed experts
of `moe_intermediate_size`, k a token, and one shared expert:

    p = sigmoid(x W_r)                         float32
    S = the k largest of p + b                 b the selection bias
    w_e = p_e / sum_{e' in S} p_e' * routed_scaling_factor       (2.5)
    MoE(x) = sum_{e in S} w_e Expert_e(x) + Shared(x)

    logits_p = RMS_f(h_p^L) W_head             untied

**The MTP module** (DeepSeek-V3 section 2.2; the checkpoint's layer 78:
`enorm`, `hnorm`, `eh_proj`, one decoder block, `shared_head.norm`; the
embedding and the head are the trunk's).  With ``h_p^L`` the trunk's last
layer's output BEFORE ``RMS_f`` and ``t_{p+1}`` the token that follows
position p:

    u_p = [RMS_e(Emb(t_{p+1})) ; RMS_h(h_p^L)] W_eh      (2d -> d)
    z   = Block(u)       a routed block of the trunk's own kind, with its
                         own indexer, rotary position p
    logits'_p = RMS_sh(z_p) W_head             predicts t_{p+2}

`assumed` (the configuration's): the halves' order (embedding first), that
the block carries an indexer, that ``h^L`` in a cut model is the last KEPT
layer's output.

**The checkpoint's layout.**  `params` hold `l<i>_qb_weight` head by head,
``[q_n,h | q_r,h]``, and every rotary part's pairs interleaved — of `W_qb`,
`W_kva`, `W_Iq`, `W_Ik` and the key LayerNorm's gain and shift; the model
under test keeps all heads' q_n, then all heads' q_r, and rotary channels
in rotate-half order (`families/glm5.py checkpoint_layout` maps its
parameters to these; a test ties the two).

**One chip's share.**  `held` ``(first, count)`` — by default the
configuration's `held_experts` — says which experts' matrices `params`
holds: the choice S and the weights stay over all E, the terms of experts
outside the range are left out, the shared expert is computed whole.  A
sliced vocabulary is a smaller one.

Departures from the published code, none in the mathematics: the indexer's
scores a block of `QUERY_BLOCK` query positions at a time, attention a
group of `HEAD_GROUP` heads and such a block at a time against a mask made
once a layer; stacked expert matrices, each held
expert applied to every position with its weight (0 where not chosen).
"""
import functools

import jax
import jax.numpy as jnp

MIXER = ("ln1_gamma", "qa_weight", "qa_norm_gamma", "qb_weight",
         "kva_weight", "kva_norm_gamma", "kvb_weight", "out_weight")
INDEXER = ("iq_weight", "ik_weight", "ik_norm_gamma", "ik_norm_beta",
           "iw_weight")
DENSE = ("ln2_gamma", "ffn1_weight", "ffn2_weight")
ROUTED = ("ln2_gamma", "router_weight", "router_bias", "gate_weight",
          "up_weight", "down_weight", "shared_gate_weight",
          "shared_up_weight", "shared_down_weight")
QUERY_BLOCK = 128
HEAD_GROUP = 16


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def _layer_norm(x, gain, shift, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + shift


def _rotary(x, theta):
    """``x (..., T, r)`` with the pairs INTERLEAVED, row t at position t."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = (x[..., 0::2].astype(jnp.float32),
                 x[..., 1::2].astype(jnp.float32))
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def geometry(config):
    """The latent attention's sizes as a dict."""
    return dict(heads=config["num_attention_heads"],
                q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
                nope=config["qk_nope_head_dim"],
                rope=config["qk_rope_head_dim"], value=config["v_head_dim"],
                theta=float(config["rope_parameters"]["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("geo", "index", "eps", "full"))
def mixer(x, p, ix, geo, index, eps, full=False):
    """``x + Mix(RMS_in(x))`` of ``x (T, d)`` (T whole blocks): `p` the
    mixer's parameters by `MIXER`'s names, `ix` the indexer's by
    `INDEXER`'s, `geo` ``geometry``'s items, `index` ``(heads, dim,
    top_k)``; `full` drops the selection (a seeded fault's).  Returns (the stream, the
    cached row ``[c | k_r] (T, r_kv + r)``, the index keys ``(T, D)``, the
    selection's margin ``(T,)``: the gap between the last kept and the
    first left-out score as a share of the kept scores' spread)."""
    geo = dict(geo)
    h, nope, rope, value, theta = (geo[k] for k in (
        "heads", "nope", "rope", "value", "theta"))
    heads_i, dim, top_k = index
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        normed = _rms(x, p["ln1_gamma"], eps)
        c_q = _rms(normed @ p["qa_weight"].T, p["qa_norm_gamma"], eps)
        kva = normed @ p["kva_weight"].T
        rank = kva.shape[-1] - rope
        c = _rms(kva[:, :rank], p["kva_norm_gamma"], eps)
        k_r = _rotary(kva[:, rank:], theta)
        # the indexer
        q_i = (c_q @ ix["iq_weight"].T).reshape(t, heads_i, dim)
        q_i = jnp.concatenate(
            [_rotary(q_i[..., :rope].transpose(1, 0, 2), theta).transpose(
                1, 0, 2), q_i[..., rope:]], axis=-1)
        k_i = _layer_norm(normed @ ix["ik_weight"].T, ix["ik_norm_gamma"],
                          ix["ik_norm_beta"], eps)
        k_i = jnp.concatenate([_rotary(k_i[:, :rope], theta),
                               k_i[:, rope:]], axis=-1)
        w_i = (normed @ ix["iw_weight"].T) * jnp.asarray(
            heads_i ** -0.5 * dim ** -0.5, normed.dtype)
        kept = min(top_k, t)
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        blocks = t // QUERY_BLOCK

        def scores(args):           # a block of queries at a time
            w_b, q_b = args
            return jnp.einsum(
                "qh,qhk->qk", w_b,
                jax.nn.relu(jnp.einsum("qhd,kd->qhk", q_b, k_i)))

        score = jax.lax.map(scores, (
            w_i.reshape(blocks, QUERY_BLOCK, heads_i),
            q_i.reshape(blocks, QUERY_BLOCK, heads_i, dim))).reshape(
                t, t).astype(jnp.float32)
        score = jnp.where(causal, score, -jnp.inf)
        best = jax.lax.top_k(score, min(kept + 1, t))[0]
        edge = best[:, kept - 1:kept]
        out = best[:, kept] if kept < t else jnp.full((t,), -jnp.inf)
        margin = (edge[:, 0] - out) / (best[:, 0] - edge[:, 0] + 1e-30)
        keep = causal if full else (score >= edge) & causal
        # attention, a group of heads and a block of queries at a time
        qb = p["qb_weight"].reshape(h, nope + rope, -1)
        kvb = p["kvb_weight"].reshape(h, nope + value, -1)
        wo = p["out_weight"].reshape(-1, h, value)
        scale = jnp.asarray((nope + rope) ** -0.5, x.dtype)
        group = min(HEAD_GROUP, h)
        y = x
        for g in range(0, h, group):
            q = jnp.einsum("tr,gfr->gtf", c_q, qb[g:g + group])
            q_n, q_r = q[..., :nope], _rotary(q[..., nope:], theta)
            kv = jnp.einsum("tr,gfr->gtf", c, kvb[g:g + group])
            k_n, v = kv[..., :nope], kv[..., nope:]

            def block(args, k_n=k_n, v=v):
                qn_b, qr_b, keep_b = args
                s = (jnp.einsum("gqd,gkd->gqk", qn_b, k_n)
                     + jnp.einsum("gqd,kd->gqk", qr_b, k_r)) * scale
                s = jnp.where(keep_b[None], s.astype(jnp.float32), -jnp.inf)
                return jnp.einsum("gqk,gkd->gqd",
                                  jax.nn.softmax(s, axis=-1).astype(v.dtype),
                                  v)

            ctx = jax.lax.map(block, (
                q_n.reshape(group, blocks, QUERY_BLOCK, nope).swapaxes(0, 1),
                q_r.reshape(group, blocks, QUERY_BLOCK, rope).swapaxes(0, 1),
                keep.reshape(blocks, QUERY_BLOCK, t)))
            ctx = ctx.transpose(1, 0, 2, 3).reshape(group, t, value)
            y = y + jnp.einsum("gtv,dgv->td", ctx, wo[:, g:g + group])
        return y, jnp.concatenate([c, k_r], axis=-1), k_i, margin


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


@functools.partial(jax.jit, static_argnames=("eps", "crossed"))
def _join(embedded, stream, enorm, hnorm, eh_weight, eps, crossed=False):
    """``[RMS_e(embedded) ; RMS_h(stream)] W_eh`` (`crossed`: a seeded
    fault's order, the stream first)."""
    with jax.default_matmul_precision("highest"):
        halves = [_rms(embedded, enorm, eps), _rms(stream, hnorm, eps)]
        return jnp.concatenate(halves[::-1] if crossed else halves,
                               axis=-1) @ eh_weight.T


def _block(x, params, config, i, cast, held, out, faults):
    """Layer i's block on ``x (T, d)``; its cached rows and margins go to
    `out`."""
    eps = float(config["rms_norm_eps"])
    p = {n: cast(params["l%d_%s" % (i, n)]) for n in MIXER}
    ix = {n: cast(params["l%d_%s" % (i, n)]) for n in INDEXER}
    top_k = config["index_topk"]
    if "short_topk" in faults:     # 64 of the published 2,048
        top_k -= max(top_k // 32, 1)
    x, latent, keys, margin = mixer(
        x, p, ix, tuple(sorted(geometry(config).items())),
        (config["index_n_heads"], config["index_head_dim"], top_k), eps,
        full="selection_dropped" in faults)
    out["latent"][i], out["index"][i] = latent, keys
    out["index_margins"].append(margin)
    if "l%d_router_weight" % i not in params:
        return dense_block(x, *[cast(params["l%d_%s" % (i, n)])
                                for n in DENSE], eps=eps)
    x, margin = routed_block(
        x, *[cast(params["l%d_%s" % (i, n)]) for n in ROUTED],
        top_k=config["num_experts_per_tok"],
        norm_topk=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]), first=held[0], eps=eps)
    out["margins"].append(margin)
    return x


def forward(params, config, tokens, rows=None, dtype=None, held=None,
            follows=None, faults=(), pad_to=0):
    """One sequence through the trunk and the MTP module: a dict of the
    trunk's `logits` and the module's `draft_logits` at the positions
    `rows` (default all) over the vocabulary `params` holds — the module's
    at position p made of ``h_p^L`` and the token at p + 1 (after the last
    one `follows`, default token 0: that row is then nobody's) —, the
    routers' `margins` ``(routed layers, T)`` (the module's last), the
    indexers' `index_margins` ``(layers, T)`` and the cached rows `latent`
    / `index` of every layer ``{layer: (T, width)}``, the module's under
    ``num_hidden_layers``.  `held` ``(first, count)``: the experts `params`
    holds (default the configuration's `held_experts`).  `dtype`: THE
    CONTROL — every weight cast to it as it is used, so that activations
    are of it too — which the family's check has to refuse.  `faults`:
    seeded faults the check has to refuse too — ``"normed_stream"`` (the
    module fed ``RMS_f(h)``), ``"crossed_halves"``, ``"selection_dropped"``,
    ``"short_topk"`` (a top-k short by a thirty-second: 64 of 2,048)."""
    cast = (lambda w: w) if dtype is None else (lambda w: w.astype(dtype))
    tokens = [int(t) for t in tokens]
    true_len = len(tokens)
    follow = tokens[1:] + [0 if follows is None else int(follows)]
    # whole blocks (`pad_to`: at least so many positions, so that several
    # sequences share one compiled shape): the pad's rows come after every
    # real one and are cut
    pad = max(pad_to - true_len, 0) + -max(pad_to, true_len) % QUERY_BLOCK
    tokens, follow = tokens + [0] * pad, follow + [0] * pad
    eps = float(config["rms_norm_eps"])
    held = tuple(held or config.get("held_experts") or (0, None))
    layers = config["num_hidden_layers"]
    out = {"margins": [], "index_margins": [], "latent": {}, "index": {}}
    embed = cast(params["embed_weight"])
    x = embed[jnp.asarray(tokens, jnp.int32)]
    for i in range(layers):
        x = _block(x, params, config, i, cast, held, out, faults)
    gamma, head = cast(params["ln_f_gamma"]), cast(params["head_weight"])
    stream = (_rms(x, gamma, eps) if "normed_stream" in faults else x)
    u = _join(embed[jnp.asarray(follow, jnp.int32)], stream,
              cast(params["mtp_enorm_gamma"]), cast(params["mtp_hnorm_gamma"]),
              cast(params["mtp_eh_weight"]), eps,
              crossed="crossed_halves" in faults)
    z = _block(u, params, config, layers, cast, held, out, faults)
    at = (jnp.arange(true_len) if rows is None
          else jnp.asarray(rows, jnp.int32))
    out["logits"] = _head(x[at], gamma, head, eps)
    out["draft_logits"] = _head(z[at], cast(params["mtp_ln_f_gamma"]), head,
                                eps)
    for k in ("latent", "index"):
        out[k] = {i: v[:true_len] for i, v in out[k].items()}
    out["margins"] = jnp.stack(out["margins"])[:, :true_len]
    out["index_margins"] = jnp.stack(out["index_margins"])[:, :true_len]
    return out


def logits(params, config, tokens, held=None):
    return forward(params, config, tokens, held=held)["logits"]


def greedy_decode(params, config, prompt, steps):
    """`steps` greedy tokens after `prompt`, one full forward a token: what
    a drafting server's reply has to equal, whatever its drafts."""
    tokens = [int(t) for t in prompt]
    for _ in range(steps):
        last = forward(params, config, tokens, rows=[len(tokens) - 1])
        tokens.append(int(jnp.argmax(last["logits"][0])))
    return tokens[len(prompt):]
