"""The `dots3` family: how a configuration file becomes the model under
test (`models.TransformerLM` with dots3-note-prev's block: latent
attention in BOTH layer kinds, each with its own heads, ranks, widths and
rotary base — a full layer's under a learned selection, the indexer's 2,048
best of the cached rows; a sliding layer's over a ring of 513 — a headwise
gate on every head's context, a rescale on the normed latents, a dense
SwiGLU in layer 0 and then 8 of 256 sigmoid-routed experts beside a shared
one — held as ONE CHIP'S SHARE of the deployment the file states), its
seeded weights, its comparison with the plain reference, and the bytes
and operations of its prefill and its decode step."""
import numpy as np

from ..reference import dots3 as reference
from .afmoe import (INIT_STD, _no_chip_favoured,
                    router_error as _router_error)
from .mistral4 import ROUTER_LOGIT_STD

KINDS = {"full_attention": "sparse_latent_attention",
         "sliding_attention": "window_latent_attention"}
BIAS_STD = 0.05
# The mean gain of the query latent's norm (`qa_norm_gamma`).  With the
# rescale of the normed latents and matrices of N(0, 0.02) a full layer's
# attention logits have a deviation of ~2 at the published widths
# (q_n . k_n over 128 channels ~1.7, the rotary part ~1.2), so a row's
# softmax over its 2,048 selected rows rests on a few dozen of them: the
# context is of the values' own size, the attention is a large part of
# the stream, and a selection that is dropped (the row then attends to all
# ~15k) moves every logit.  1.0 keeps that; a smaller gain flattens the
# softmax until a dropped selection hides in the rounding (PERF.md
# section 6, PR 48, has the readings).
QUERY_GAIN = 1.0
# Embedding rows N(0, EMBED_STD).  With every matrix N(0, 0.02) a layer's
# output is 15-70 times the stream it joins (an embedding row has a norm
# of 1.4, a full layer's attention output of ~22, the dense MLP's of
# ~100), so layer 0's attention IS the stream its MLP norms, and whatever
# moves a twentieth of the attention moves a twentieth of every logit.
# Program and reference choose their 2,048 rows from scores that differ by
# the program's rounding, and with seeded weights the indexer's choice is
# independent of the attention's: the ~1.5% of rows that differ at the
# edge of the choice carry as much attention as any, and moved the logits
# by 9% (median; my chip run, PR 48, N(0, 0.02) rows) — four times what
# the reference in bfloat16 differs by.  A trained model's stream is large
# against any one layer's update; rows of N(0, 4) (a norm of 286) make the
# full layers' attention ~8% of the stream they join: the differing rows
# then move a logit by under 1%, one bfloat16 pass shows again (1.8 times
# the sound reading), and a selection that is dropped still moves every
# logit by 10%, one short by 64 of 2,048 by 2% (the readings: PERF.md
# section 6, PR 48: medians 3.9 / 2.0 / 0.96% at N(0, 1) / N(0, 2) / N(0,
# 4), the control's 5.3 / 3.1 / 1.76%).
EMBED_STD = 4.0

# THE CHECK, through the timed tenant's own programs and rings, with EVERY
# SLOT LIVE: one prompt a slot, each prefilled alone through the tenant's
# LARGEST bucket's prefill program — the up-projected form under the
# selection's mask, whose rows fill the latent rings, the index keys and
# the window rings — then CHECK_STEPS greedy decode steps of ALL rows at
# once through the decode program of as many rows as the tenant has slots:
# the absorbed form over the gathered rows and over the wrapped rings.
# Every row's logits (over the vocabulary slice), of its prefill and of
# each step, against ONE blocked float32 forward of the reference over
# that row's final sequence; and the rows every layer's cache entries hold
# for that slot afterwards — latent rows, index keys, a window ring's rows
# where `position mod W` puts them — against the reference's.  The rows,
# in slots drawn from the seed: ONE prompt LONG_SHORT short of the bucket
# (15,352 at 15,360), one an eighth of `index_topk` past `index_topk`
# (2,304: the selection has just begun to bind), the others spread between
# (6,485 and 10,922 at four slots).  Every compared row has more than
# `index_topk` positions behind it.
LONG_SHORT = 8
CHECK_STEPS = 128
# Rows where the reference's router has a near tie THAT THIS CHIP FEELS in
# any routed layer are counted and skipped, as every routed family's are
# (families/mistral4.py says why); the margin is `reference.route`'s.
NEAR_TIE = 0.005
# The INDEXER'S edge is no such tie and no row is skipped for it.  A row
# with more than `index_topk` positions behind it has thousands of scores
# on a line: its 2,048th and 2,049th lie 0.004% of the kept scores' spread
# apart at ~15k positions (median; 0.02% at 2,304, the largest of any row
# 0.2%: the reference's `index_margins` in my chip runs), and one bfloat16
# pass moves a score by 0.6-1.2% of that spread (a float32 reckoning of
# layer 0's indexer at the published widths on the CPU, PR 48: no row of
# 64 at 6.5k or 15k positions, and 16% at 2,304, keeps the very same 2,048
# under bfloat16 operands).  `index_tie_share`, the share of the compared rows
# whose margin is under INDEX_TIE, says so in every run's line: it reads
# 100%, and skipping by it would leave nothing to compare.  What the edge
# costs is bounded instead: `selection_overlap` counts it and EMBED_STD
# keeps what it moves small.
INDEX_TIE = 0.005
# LIMITS, each a share of the row's largest |reference logit| (the cache
# rows': of the entry's largest |reference value|).
#   LOGIT_RTOL        the MEDIAN of the compared rows, of all and of each
#                     prompt by itself, the largest: what is wrong in
#                     every row, or in every row of one context length.
#   LOGIT_RTOL_NEAR   the median of the prompt just past `index_topk`
#                     (2,304): its rows keep 2,048 of 2.3k, so program and
#                     reference hardly differ in what they select and the
#                     reading is the program's rounding alone — the
#                     sharpest of the limits for a lower precision.
#   LOGIT_RTOL_HIGH   the HIGH_QUANTILE of all compared rows.
#   LOGIT_RTOL_WORST  the WORST compared row: one row wrong.
#   CACHE_RTOL_FIRST  the worst cached row of layer 0's entries (latent
#                     rows and index keys made from the embedding alone):
#                     a row written to the wrong place, unrotated,
#                     unscaled, unnormed, in a lower precision.
#   CACHE_RTOL        the worst cached row of any later entry.
# Program and reference choose their 2,048 rows from scores that differ by
# the program's rounding, so at the edge of the choice the two sets differ
# in a few rows (`selection_overlap`: 99.77-99.78% with the program's
# cached index keys in the reference's place, 98.4% before EMBED_STD); what
# that moves is part of the sound readings, most of all of the worst row
# and the worst cached row, which no precision moves and whose limits only
# bound a row that is WRONG.
# Readings (my chip runs, PR 48, TPU v5e).  SOUND, 37 seeds — 25 runs of
# 64 steps (208-234 compared rows each) and 12 of 128 (~440 rows; eight of
# them with their first 64 steps read apart too): medians' largest
# 1.03-1.23% (all rows 0.87-0.99%), the near prompt 0.50-0.57%, layer 0's
# cached rows 0.24-0.31%, the worst later cached row 3.6-5.7%; the 0.9
# quantile 1.37-1.84% over 64 steps (deviation 0.10) and 1.47-1.70% over
# 128 (0.06): what made CHECK_STEPS 128; the worst row 2.3-7.5%, with a
# HEAVY tail that is the longest prompt's alone (of eight seeds' 3,531 rows
# 31 / 7 / 3 / 2 / 0 read over 3 / 4 / 5 / 6 / 7%, every one over 4% at
# 15,352 positions: one differing row at the edge that a few heads rest
# on).  CONTROL, the reference with weights and activations in bfloat16 in
# the program's place ON THE SAME SEQUENCES (`control="bfloat16"`), 16
# seeds: 1.87-2.02% / 1.32-1.44% / 2.24-2.46%, worst row 3.3-6.3% (no
# precision moves it), refused by each of the first three limits on every
# seed; `selection_dropped` (four seeds): 11.6-11.9% / 3.9-4.2% /
# 12.5-12.8% / 15.2-16.3%, cached rows 12.3-12.9%; a top-k SHORT BY 64 of
# its 2,048 (four seeds): 2.08-2.20% / 1.92-2.08% / 2.87-3.01% / 4.7-6.1%,
# refused by three.  The first three limits are the geometric middles of
# the sound side's largest and the control's smallest (the near prompt's:
# 2.3 times apart); the later cached rows' stands 1.7 times over the sound
# side's largest (a row written wrong reads 50% and more), layer 0's three
# times.  The worst row's stood at 8% until its tail was read: by the rows
# above one run in ~30 to ~300 would have passed it with nothing wrong (a
# later run read 7.5%).  At 15% it bounds a row that is WRONG (a row that
# reads another's pages, or garbage, in every layer moves more than the
# dropped selection's 15%, which changes two layers of five), twice the
# largest sound reading, and a sound row passes it in one run of 200 to 900
# by a power-law tail and never by an exponential one; whatever is wrong in a
# tenth of the rows meets the quantile's limit first.
LOGIT_RTOL = 1.5e-2
LOGIT_RTOL_NEAR = 0.9e-2
LOGIT_RTOL_HIGH = 2.0e-2
LOGIT_RTOL_WORST = 0.15
CACHE_RTOL_FIRST = 1e-2
CACHE_RTOL = 0.1
HIGH_QUANTILE = 0.9
NEAR = 1          # the near prompt's place in `check_plans`
ROUTER_RTOL = 1e-4


def held_experts(config):
    """(first, count) of the routed experts this chip holds."""
    first, count = config["held_experts"]
    assert count == config["n_routed_experts"]
    return int(first), int(count)


def kind_specs(config):
    """`TransformerLM`'s `kind_specs`: each layer kind's own sizes."""
    specs = {}
    for kind, name in KINDS.items():
        geo = reference.geometry(config, kind)
        gate = ("attention_gate_type" if kind == "full_attention"
                else "swa_attention_gate_type")
        assert config[gate] == "headwise"
        specs[name] = dict(
            num_heads=geo["heads"], q_rank=geo["q_rank"],
            kv_rank=geo["kv_rank"], nope_dim=geo["nope"],
            rope_dim=geo["rope"], value_dim=geo["value"],
            rope_theta=geo["theta"], head_gate=True,
            lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]))
    specs[KINDS["full_attention"]].update(
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"], index_topk=config["index_topk"])
    specs[KINDS["sliding_attention"]]["window"] = config[
        "sliding_window_size"]
    return specs


def model_args(config):
    """`TransformerLM`'s arguments for this configuration."""
    layers, dense = config["num_hidden_layers"], config[
        "first_k_dense_replace"]
    assert config["rope_scaling"] is None and config["moe_layer_freq"] == 1
    assert config["scoring_func"] == "sigmoid"
    assert config["topk_method"] == "noaux_tc" and "n_group" not in config
    assert config["n_shared_experts"] == 1 and not config["attention_bias"]
    assert len(config["layer_types"]) == layers
    return dict(
        vocab=config["vocab_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="none",
        bias=False, tied_head=config["tie_word_embeddings"], ffn="swiglu",
        layer_types=[KINDS[k] for k in config["layer_types"]],
        kind_specs=kind_specs(config),
        ffn_types=["dense"] * dense + ["routed"] * (layers - dense),
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        shared_d_ff=(config["n_shared_experts"]
                     * config["moe_intermediate_size"]),
        router_score=config["scoring_func"], router_bias=True,
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def param_shapes(config):
    d, v = config["hidden_size"], config["vocab_size"]
    ff, xf = config["intermediate_size"], config["moe_intermediate_size"]
    sf = config["n_shared_experts"] * xf
    total, held = config["router_experts"], held_experts(config)[1]
    j, dim = config["index_n_heads"], config["index_head_dim"]
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    for i, kind in enumerate(config["layer_types"]):
        g = reference.geometry(config, kind)
        h, nope, rope, value = g["heads"], g["nope"], g["rope"], g["value"]
        layer = {"ln1_gamma": (d,), "qa_weight": (g["q_rank"], d),
                 "qa_norm_gamma": (g["q_rank"],),
                 "qb_weight": (h * (nope + rope), g["q_rank"]),
                 "kva_weight": (g["kv_rank"] + rope, d),
                 "kva_norm_gamma": (g["kv_rank"],),
                 "kvb_weight": (h * (nope + value), g["kv_rank"]),
                 "out_weight": (d, h * value), "hgate_weight": (h, d),
                 "ln2_gamma": (d,)}
        if kind == "full_attention":
            layer.update({"iq_weight": (j * dim, g["q_rank"]),
                          "ik_weight": (dim, d), "ik_norm_gamma": (dim,),
                          "ik_norm_beta": (dim,), "iw_weight": (j, d)})
        if i < config["first_k_dense_replace"]:
            layer.update({"ffn1_weight": (2 * ff, d), "ffn2_weight": (d, ff)})
        else:
            layer.update({
                "router_weight": (d, total), "router_bias": (total,),
                "gate_weight": (held, d, xf), "up_weight": (held, d, xf),
                "down_weight": (held, xf, d), "shared_gate_weight": (d, sf),
                "shared_up_weight": (d, sf), "shared_down_weight": (sf, d)})
        for n, s in layer.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in and in the PROGRAM'S layout (`checkpoint_layout` turns them
    to the published one): matrices N(0, INIT_STD), the embedding's rows
    N(0, EMBED_STD); every
    gain 1 + N(0, 0.1) (the query latent's QUERY_GAIN + N(0, 0.1)) and the
    LayerNorm's shift N(0, 0.1), so that a norm that is dropped or crossed
    shows; the router N(0, ROUTER_LOGIT_STD / sqrt(d)) and its selection
    bias N(0, BIAS_STD), as `afmoe._no_chip_favoured` leaves them.  One
    jitted call a tensor (one program a shape)."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 7.3 GB of weights are made
    model(config)
    dtype = jnp.dtype(config["param_dtype"])
    router_std = ROUTER_LOGIT_STD / config["hidden_size"] ** 0.5

    @functools.partial(jax.jit, static_argnames=("shape",))
    def normal(key, mean, std, shape):
        return mean + std * jax.random.normal(key, shape, dtype)

    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            gain = name.endswith("_gamma")
            mean = (QUERY_GAIN if name.endswith("_qa_norm_gamma")
                    else float(gain))
            std = (0.1 if gain or name.endswith("_beta") else router_std
                   if name.endswith("_router_weight") else BIAS_STD
                   if name.endswith("_router_bias") else EMBED_STD
                   if name == "embed_weight" else INIT_STD)
            out[name] = normal(jax.random.fold_in(key, i), mean, std, shape)
            if name.endswith(("_router_weight", "_router_bias")):
                out[name] = _no_chip_favoured(out[name],
                                              held_experts(config)[1])
    return out


def layout_rows(config, kind):
    """For each row of the PROGRAM'S `W_qb` of a layer of `kind`, the row
    of the published checkpoint's it holds: the program keeps all heads'
    q_nope, then all heads' q_rope; the checkpoint each head's ``[q_nope |
    q_rope]`` together."""
    g = reference.geometry(config, kind)
    head = np.arange(g["heads"])[:, None] * (g["nope"] + g["rope"])
    return np.concatenate([(head + np.arange(g["nope"])).ravel(),
                           (head + g["nope"] + np.arange(g["rope"])).ravel()])


def checkpoint_layout(params, config):
    """`params` with every `W_qb` as the published checkpoint lays it out
    (what `reference/dots3.py` takes): the inverse of `layout_rows`."""
    out = dict(params)
    for i, kind in enumerate(config["layer_types"]):
        name = "l%d_qb_weight" % i
        out[name] = params[name][np.argsort(layout_rows(config, kind))]
    return out


def router_error(params, config):
    """The program's router function against the float32 product at
    "highest", on 64 rows of unit noise and the first routed layer's
    router: the largest difference as a share of the largest logit."""
    return _router_error(params, dict(
        config, num_dense_layers=config["first_k_dense_replace"]))


def check_plans(session, config):
    """Each slot's prompt length: one LONG_SHORT short of the largest
    bucket, one an eighth of `index_topk` past it, the others between."""
    bucket, slots = max(session._seq_ladder), session._slots
    top = min(config["index_topk"], bucket - LONG_SHORT - 1)
    plans = [bucket - LONG_SHORT, top + max(top // 8, 1)]
    plans += [top + (bucket - top) * j // (slots - 1)
              for j in range(1, slots - 1)]
    return plans[:slots]


def _serve(session, prompts, slots, steps, vocab):
    """Row r's prompt prefilled ALONE into ``slots[r]`` through the
    largest bucket's prefill program, then `steps` greedy decode steps of
    ALL the rows in ONE call each, through the decode program of as many
    rows: (logits ``(rows, 1 + steps, vocab)``, each row's tokens)."""
    bucket = max(session._seq_ladder)
    toks = [list(p) for p in prompts]
    got = np.zeros((len(prompts), 1 + steps, vocab), np.float32)
    exe, fn = session._program(session._prefill_pred, 1, bucket, True)
    for r, prompt in enumerate(prompts):
        data = np.zeros((1, bucket), np.float32)
        data[0, :len(prompt)] = prompt
        got[r, 0] = session._run(
            exe, fn, data, np.full((1,), slots[r], np.float32),
            np.full((1,), len(prompt), np.float32))[0]
    assert len(prompts) == session._decode_ladder[-1]
    exe, fn = session._program(session._decode_pred, len(prompts), 1, False)
    slot = np.asarray(slots, np.float32)
    for step in range(steps):
        tokens = got[:, step].argmax(axis=-1)
        length = np.asarray([len(t) for t in toks], np.float32)
        for t, token in zip(toks, tokens):
            t.append(int(token))
        got[:, step + 1] = session._run(
            exe, fn, tokens[:, None].astype(np.float32), slot, length)
    return got, toks


def _cached_rows(session, slot, filled):
    """What every cache entry holds for `slot` once `filled` positions are
    cached: ``{entry name: (rows (positions, width), the sequence position
    of each row)}`` — a ring shorter than `filled` holds the newest
    position of each residue."""
    out = {}
    for name, value in zip(session._spec, session._state):
        ring = value.shape[3]
        r = np.arange(min(ring, filled))
        newest = r + ring * np.maximum((filled - 1 - r) // ring, 0)
        out[name] = (np.asarray(value[slot, 0, :, :len(r)]).T, newest)
    return out


def check_rows(config, session, params, seed, control=None,
               steps=CHECK_STEPS):
    """The rows of the check the module's head describes, served and
    compared: `err` (each compared position's largest logit difference as a
    share of the row's largest |reference logit|), `margin` (the reference
    router's over the held experts, the least over the routed layers),
    `index_margin` (the reference indexers' own, the least over the full
    layers), `prompt` (the row's index), `cache` (entry name -> the worst
    cached row's error), `overlap` (of the positions the reference's indexers
    choose for the compared rows, the share chosen too when the PROGRAM'S
    cached index keys stand in for the reference's own), `finite`,
    `prompts`.  `control`: a dtype in which the
    REFERENCE, on the sequences the program generated, stands in for the
    program's logits."""
    rng = np.random.default_rng(seed)
    plans = check_plans(session, config)
    steps = min(steps, session._max_len - max(plans))
    prompts = [[int(t) for t in rng.integers(0, config["vocab_size"], n)]
               for n in plans]
    slots = rng.permutation(session._slots)[:len(plans)]
    got, seqs = _serve(session, prompts, slots, steps, config["vocab_size"])
    published = checkpoint_layout(params, config)
    out = {"err": [], "margin": [], "index_margin": [], "prompt": []}
    cache, chosen, shared = {}, 0, 0
    for r, (n, toks, mine) in enumerate(zip(plans, seqs, got)):
        rows = list(range(n - 1, n + steps))
        held = _cached_rows(session, int(slots[r]), n + steps)
        keys = {int(name.rsplit("_", 1)[1]): rows_held
                for name, (rows_held, _) in held.items()
                if name.startswith("index")}
        ref = reference.forward(published, config, toks, rows=rows,
                                keep_rows=rows, index_keys=keys)
        for mine_k, with_k in zip(ref["keep"], ref["keep_with"]):
            chosen += int(np.asarray(mine_k).sum())
            shared += int(np.asarray(mine_k & with_k).sum())
        want = np.asarray(ref["logits"], np.float64)
        if control is not None:
            mine = np.asarray(reference.forward(
                published, config, toks, rows=rows, dtype=control)["logits"],
                np.float32)
        out["err"].extend(np.abs(mine - want).max(axis=-1)
                          / np.abs(want).max(axis=-1))
        out["margin"].extend(np.asarray(ref["margins"]).min(axis=0)[rows])
        out["index_margin"].extend(
            np.asarray(ref["index_margins"]).min(axis=0)[rows])
        out["prompt"].extend([r] * len(rows))
        for name, (rows_held, at) in held.items():
            i = int(name.rsplit("_", 1)[1])
            kind = "index" if name.startswith("index") else "latent"
            theirs = np.asarray(ref[kind][i], np.float64)[at]
            cache[name] = max(cache.get(name, 0.0), float(
                np.abs(rows_held - theirs).max() / np.abs(theirs).max()))
    out = {k: np.asarray(v) for k, v in out.items()}
    return dict(out, cache=cache, overlap=shared / max(chosen, 1),
                finite=bool(np.isfinite(got).all()), prompts=plans,
                steps=steps)


def judge(rows, router_rel_err, control=None):
    """(ok, facts) of `check_rows`' rows by the limits above."""
    errs, prompt = rows["err"], rows["prompt"]
    clear = rows["margin"] >= NEAR_TIE

    def stat(mask, reduce):
        return float(reduce(errs[mask])) if mask.any() else float("inf")

    by_prompt = [stat(clear & (prompt == r), np.median)
                 for r in range(len(rows["prompts"]))]
    first = max(v for k, v in rows["cache"].items() if k.endswith("_0"))
    cache_worst = max(rows["cache"].values())
    facts = {"logit_rel_err": stat(clear, np.median),
             "logit_rel_err_by_prompt": by_prompt,
             "logit_rel_err_near": by_prompt[NEAR],
             "logit_rel_err_high": stat(
                 clear, lambda e: np.quantile(e, HIGH_QUANTILE)),
             "logit_rel_err_worst": stat(clear, np.max),
             "logit_rel_err_skipped": stat(~clear, np.max),
             "cache_rel_err": cache_worst, "cache_rel_err_first": first,
             "cache_rel_errs": rows["cache"],
             "selection_overlap": rows["overlap"],
             "index_margin_median": float(np.median(rows["index_margin"])),
             "index_tie_share": float(
                 (rows["index_margin"] < INDEX_TIE).mean()),
             "router_rel_err": router_rel_err,
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some routed layer a held expert's "
             "score lies closer to the edge of the reference's choice than "
             "near_tie of the last kept probability",
             "rows_a_step": len(rows["prompts"]), "steps": rows["steps"],
             "prompts": rows["prompts"], "control": control,
             "limits": {"median": LOGIT_RTOL, "near": LOGIT_RTOL_NEAR,
                        "q%d" % round(100 * HIGH_QUANTILE): LOGIT_RTOL_HIGH,
                        "worst": LOGIT_RTOL_WORST, "cache": CACHE_RTOL,
                        "cache_first": CACHE_RTOL_FIRST,
                        "router": ROUTER_RTOL, "near_tie": NEAR_TIE,
                        "index_tie": INDEX_TIE}}
    ok = (rows["finite"]
          and max(by_prompt + [facts["logit_rel_err"]]) <= LOGIT_RTOL
          and by_prompt[NEAR] <= LOGIT_RTOL_NEAR
          and facts["logit_rel_err_high"] <= LOGIT_RTOL_HIGH
          and facts["logit_rel_err_worst"] <= LOGIT_RTOL_WORST
          and (control is not None or (cache_worst <= CACHE_RTOL
                                       and first <= CACHE_RTOL_FIRST))
          and router_rel_err <= ROUTER_RTOL)
    return bool(ok), facts


def check_against_reference(config, session, params, seed, bucket=None,
                            control=None, steps=CHECK_STEPS):
    """`check_rows` judged by the limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  `bucket` (the harness hands the tenant's smallest) is not
    used: every row goes through the largest.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, control, steps)
    return judge(rows, router_error(params, config), control)


# ----------------------------------------------------------------------
# bytes and operations, for the hand rooflines (PERF.md section 5)
# ----------------------------------------------------------------------

def _layers(config, kind):
    return sum(k == kind for k in config["layer_types"])


def mixer_params(config, kind):
    """Parameters of one layer's mixer matrices (the indexer's among a
    full layer's)."""
    d = config["hidden_size"]
    g = reference.geometry(config, kind)
    h = g["heads"]
    n = (d * g["q_rank"] + g["q_rank"] * h * (g["nope"] + g["rope"])
         + d * (g["kv_rank"] + g["rope"])
         + g["kv_rank"] * h * (g["nope"] + g["value"])
         + h * g["value"] * d + h * d)
    if kind == "full_attention":
        j, dim = config["index_n_heads"], config["index_head_dim"]
        n += g["q_rank"] * j * dim + d * dim + d * j
    return n


def _ffn_params(config):
    """(dense layers' MLPs, one routed layer's shared expert and router,
    one routed expert) in parameters."""
    d = config["hidden_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    return (3 * d * config["intermediate_size"],
            config["n_shared_experts"] * expert
            + d * config["router_experts"], expert)


def index_bytes(config, rows, ring_len):
    """Index keys ONE decode step scores, all full layers: each row's
    whole page of ``index_head_dim`` float32 lines of `ring_len`
    positions (the ``jax.numpy`` body reads pages whole)."""
    return (_layers(config, "full_attention") * rows * 4
            * config["index_head_dim"] * ring_len)


def sparse_read_bytes(config, lengths, ring_len=None):
    """Latent rows ONE decode step reads for rows at `lengths`: a full
    layer's ``min(length + 1, index_topk)`` gathered rows of 576 floats, a
    sliding layer's whole ring of ``min(window, ring_len)`` rows of 1,088."""
    full, slide = (reference.geometry(config, k)
                   for k in ("full_attention", "sliding_attention"))
    window = min(config["sliding_window_size"], ring_len or float("inf"))
    chosen = sum(min(n + 1, config["index_topk"]) for n in lengths)
    return (_layers(config, "full_attention") * 4
            * (full["kv_rank"] + full["rope"]) * chosen
            + _layers(config, "sliding_attention") * 4
            * (slide["kv_rank"] + slide["rope"]) * window * len(lengths))


def step_bytes(config, rows, lengths, experts_hit, ring_len=16384):
    """Bytes ONE decode step of `rows` rows reads, by part: every weight
    outside the routed experts once, `experts_hit` (a layer) of the held
    experts' matrices, the index keys, the latent rows."""
    d, v = config["hidden_size"], config["vocab_size"]
    dense, shared, expert = _ffn_params(config)
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return {"mixers": 4 * sum(mixer_params(config, k)
                              for k in config["layer_types"]),
            "dense_mlp": 4 * config["first_k_dense_replace"] * dense,
            "shared_and_router": 4 * routed * shared,
            "experts": 4 * routed * experts_hit * expert,
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "index": index_bytes(config, rows, ring_len),
            "rows": sparse_read_bytes(config, lengths, ring_len)}


def prefill_flops(config, tokens):
    """Multiply-adds x 2 of ONE prefill of `tokens` positions, by part, as
    the MATHEMATICS counts them (a causal pair once, a window's pairs
    alone; the mask keeps the dense count: the selection spares a prefill
    no product): the matrix products of the mixers, the MLP, the shared
    experts, the routers and the routed pairs that land on held experts
    under uniform routing; the masked attention's scores and context; the
    indexer's scores."""
    t = tokens
    dense, shared, expert = _ffn_params(config)
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    held_share = held_experts(config)[1] / config["router_experts"]
    full, slide = (reference.geometry(config, k)
                   for k in ("full_attention", "sliding_attention"))
    window = min(config["sliding_window_size"], t)
    pairs = t * (t + 1) // 2
    window_pairs = window * (window + 1) // 2 + (t - window) * window

    def attended(g, n):
        return 2 * g["heads"] * n * (g["nope"] + g["rope"] + g["value"])

    return {"matmuls": 2 * t * (
                sum(mixer_params(config, k) for k in config["layer_types"])
                + config["first_k_dense_replace"] * dense
                + routed * (shared + config["num_experts_per_tok"]
                            * held_share * expert))
            + 2 * config["hidden_size"] * config["vocab_size"],
            "attention": (_layers(config, "full_attention")
                          * attended(full, pairs)
                          + _layers(config, "sliding_attention")
                          * attended(slide, window_pairs)),
            "indexer": (_layers(config, "full_attention") * 2
                        * config["index_n_heads"] * config["index_head_dim"]
                        * pairs)}
