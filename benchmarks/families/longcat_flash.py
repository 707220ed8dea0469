"""The `longcat_flash` family: how a configuration file becomes the model
under test (`models.TransformerLM` with LongCat-Flash's block: a published
layer is TWO layers here — two latent-attention sublayers with rescaled
latents and a value narrower than the key, two dense SwiGLUs, and a routed
layer forked from the first FFN's input whose result joins at the second
FFN's end, with 256 zero-compute experts behind 512 real ones in ONE
softmax router — held as ONE CHIP'S SHARE of the deployment the file
states: its experts AND its heads), its seeded weights, its comparison
with the plain reference, and the bytes and operations of its programs."""
import numpy as np

from ..reference import longcat_flash as reference
from .afmoe import INIT_STD, _no_chip_favoured, router_error as _router_error
from .mistral4 import (CROSSING, HIGH_QUANTILE, LONG, MID, ROUTER_LOGIT_STD,
                       ROUTER_RTOL, SHORT, _latent, checkpoint_layout)
from .qwen3_next import _serve_rows

# The selection bias N(0, BIAS_STD), every chip's 8 columns alike (and the
# zero-compute experts' 32 groups of 8): the 12th largest of 768 softmax
# probabilities is ~0.01 and its neighbours lie ~0.0005 apart, so a bias
# of a fifth of it moves about every other choice — a dropped bias, or one
# added to the weights (6 * 0.002 on weights of ~0.06), shows — and
# favours no chip and neither kind of expert.
BIAS_STD = 2e-3

# THE CHECK, through the timed tenant's own programs and rings, with EVERY
# SLOT LIVE (`families/mistral4.py`'s, whose row kinds these are): one
# prompt a slot, each prefilled alone through the prefill program of its
# bucket — the UP-PROJECTED form at the key's width, whose 576-wide rows
# fill the two latent rings of every published layer — then `steps` greedy
# decode steps of ALL rows at once through the decode program of as many
# rows as the tenant has slots — the ABSORBED form over the rings.  Every
# row's logits (over the vocabulary slice), of its prefill and of each
# step, against ONE blocked float32 forward of the reference over that
# row's final sequence.  The rows, in slots drawn from the seed:
#
# (a) up to CHECK_PROMPTS short prompts of CHECK_PROMPT_LEN through the
#     SMALLEST bucket: contexts of 24-288;
# (b) ONE prompt LONG_SHORT short of the largest bucket through that
#     bucket (at 2,048 the one the TPU's blockwise prefill kernel takes, a
#     head 192 wide): a page filled to 2,040 and stepped to the ring's end;
# (c) in every other slot a prompt MID_SHORT short of its bucket, through
#     the tenant's buckets in turn: at the cell's 512 / 768 / 1,024 /
#     1,536 each crosses a boundary of the ring kernel's 384-position
#     blocks a few steps in, behind 136 positions of pad.
CHECK_PROMPTS = 4
CHECK_PROMPT_LEN = 24
LONG_SHORT = 8
LONG_STEPS = 264
MID_SHORT = 136
# Rows where the reference's router has a near tie THAT THIS CHIP FEELS in
# any layer are counted and skipped (mistral4's reason: one bfloat16 pass
# against float32 at "highest" leaves the normed stream a part in a
# hundred apart and a router logit as much; where two candidates lie
# closer the two sides keep different columns — another rounding of the
# same model).  Two margins, `reference.route`'s, each a share of the
# twelfth probability: a HELD expert's distance from the edge of the
# choice (its term is ~0.06 of an expert's output in a direction of its
# own) and a ZERO-COMPUTE expert's from the nearest column across the edge
# that is not zero-compute (its term is ~0.06 x1, nearly along the stream,
# and two of them changing places change nothing).  A flip between two
# real experts of other chips changes no term computed here and skips no
# row.  READ ON THE CHIP (my chip runs, PR 62, seed 11, 2,120 rows): the
# rows' error does NOT fall with either margin — median / 0.9 quantile /
# worst 3.32 / 4.25 / 6.29% over all rows, 3.32 / 4.24 / 6.04% over the
# 29% left at margins of 0.15 and 0.02, 3.30 / 4.30 / 5.30% over the 7%
# left at 0.05 of the zero-compute margin: an identity expert that changes
# places moves the stream ALONG ITSELF, which the next norm takes out, and
# a held expert's ~1 pair a layer-step is too rare to show.  So the margins
# are small — they skip the 27-29% of rows nearest a flip, whose worst row
# read 7.58% once where the compared rows' worst is 5.6-7.5 — and the floor on
# the compared share is high.
NEAR_TIE_HELD = 0.05
NEAR_TIE_ZERO = 0.005
# of all rows, the share that must be left to compare (read: 70.1-74.0%)
MIN_COMPARED_SHARE = 0.5
# LIMITS, each a share of the row's largest |reference logit| (mistral4's
# three: the largest of the kinds' MEDIANS — all, short, mid, long, at a
# crossing —, the HIGH_QUANTILE of all compared rows, the WORST row).
# Readings (my chip runs, PR 62, TPU v5e, at the margins above).  SOUND,
# eighteen seeds (twelve untraced runs of the cell, three traced, three
# probes): medians' largest 3.35-3.56% (all rows 3.29-3.35%), 0.9 quantile
# 4.15-4.25%, worst 5.58-7.53% (sixteen of eighteen under 7%).  It is
# rounding and no fault: eight layers deep, latents rescaled by 2 and 3.46
# before their up-projections and attention logits of deviation ~2.5, the
# one-bfloat16-pass program stands at 0.53-0.54 of the bfloat16 control on
# every seed, as mistral4's does (0.6).  CONTROL, the reference with weights
# and activations in bfloat16 ON THE SAME SEQUENCES (`control="bfloat16"`,
# three seeds): 6.73-6.81% / 7.82-8.12% / 12.07-14.18%, refused by each of
# the three limits on every seed.  LOGIT_RTOL and LOGIT_RTOL_HIGH are the
# geometric middles of the sound side's largest and the control's smallest;
# LOGIT_RTOL_WORST, the largest of ~1,500 rows and the least steady
# reading, stands a fifth over the sound side's largest and a quarter
# under the control's smallest.
LOGIT_RTOL = 4.9e-2
LOGIT_RTOL_HIGH = 5.8e-2
LOGIT_RTOL_WORST = 9.0e-2
# compared rows of (b) and at a crossing: 190-199 and 39-41 were read
MIN_LONG_COMPARED = 64
MIN_CROSSING_COMPARED = 16


def held_experts(config):
    """(first, count) of the real routed experts this chip holds."""
    first, count = config["held_experts"]
    assert count == config["n_routed_experts"]
    return int(first), int(count)


def model_args(config):
    """`TransformerLM`'s arguments for this configuration: two of its
    layers a published one."""
    layers = 2 * config["num_layers"]
    assumed = config["assumed"]
    assert config["zero_expert_type"] == "identity"
    assert config["attention_method"] == "MLA" and not config["attention_bias"]
    assert config["mla_scale_q_lora"] and config["mla_scale_kv_lora"]
    assert config["held_heads"][1] == config["num_attention_heads"]
    assert not (assumed["norm_topk_prob"]["value"]
                or assumed["router_bias"]["value"]
                or assumed["tie_word_embeddings"]["value"])
    return dict(
        vocab=config["vocab_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        d_model=config["hidden_size"], d_ff=config["ffn_hidden_size"],
        max_len=config["max_position_embeddings"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="none",
        rope_theta=config["rope_theta"], bias=False, tied_head=False,
        ffn="swiglu", layer_types=["latent_attention"] * layers,
        latent_q_rank=config["q_lora_rank"],
        latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"],
        latent_rope_dim=config["qk_rope_head_dim"],
        latent_value_dim=config["v_head_dim"],
        latent_lora_rescale=True,
        ffn_types=["shortcut", "dense"] * config["num_layers"],
        num_experts=config["router_experts"] - config["zero_expert_num"],
        zero_experts=config["zero_expert_num"],
        experts_per_token=config["moe_topk"],
        expert_d_ff=config["expert_ffn_hidden_size"],
        router_score="softmax", router_bias=True, route_norm=False,
        route_scale=float(config["routed_scaling_factor"]),
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def param_shapes(config):
    d, v = config["hidden_size"], config["vocab_size"]
    h, nope, rope, value, q_rank, kv_rank = _latent(config)
    f, xf = config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    wide, held = config["router_experts"], held_experts(config)[1]
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    sublayer = {"ln1_gamma": (d,), "qa_weight": (q_rank, d),
                "qa_norm_gamma": (q_rank,),
                "qb_weight": (h * (nope + rope), q_rank),
                "kva_weight": (kv_rank + rope, d),
                "kva_norm_gamma": (kv_rank,),
                "kvb_weight": (h * (nope + value), kv_rank),
                "out_weight": (d, h * value), "ln2_gamma": (d,),
                "ffn1_weight": (2 * f, d), "ffn2_weight": (d, f)}
    routed = {"router_weight": (d, wide), "router_bias": (wide,),
              "gate_weight": (held, d, xf), "up_weight": (held, d, xf),
              "down_weight": (held, xf, d)}
    for i in range(2 * config["num_layers"]):
        for n, s in dict(sublayer, **({} if i % 2 else routed)).items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in and in the PROGRAM'S layout (`checkpoint_layout` turns them
    to the published one): matrices and embeddings N(0, INIT_STD); every
    gain 1 + N(0, 0.1), so that a norm that is dropped or crossed shows;
    the router N(0, ROUTER_LOGIT_STD / sqrt(d)) and its selection bias
    N(0, BIAS_STD), every group of 8 columns — each chip's experts, and
    the zero-compute experts by eights — summing to zero and biased alike,
    so that the draw favours no chip and neither kind of expert
    (`afmoe._no_chip_favoured`).  One jitted call a tensor."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 13.7 GB of weights are made
    model(config)
    dtype = jnp.dtype(config["param_dtype"])
    stds = {"_gamma": 0.1, "_router_bias": BIAS_STD,
            "_router_weight": ROUTER_LOGIT_STD / config["hidden_size"] ** 0.5}

    @functools.partial(jax.jit, static_argnames=("shape",))
    def normal(key, mean, std, shape):
        return mean + std * jax.random.normal(key, shape, dtype)

    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            kind = next((k for k in stds if name.endswith(k)), None)
            out[name] = normal(jax.random.fold_in(key, i),
                               float(kind == "_gamma"),
                               stds.get(kind, INIT_STD), shape)
            if kind in ("_router_weight", "_router_bias"):
                out[name] = _no_chip_favoured(out[name],
                                              held_experts(config)[1])
    return out


def router_error(params, config):
    """`afmoe.router_error` on layer 0's router."""
    return _router_error(params, dict(config, num_dense_layers=0))


def check_plans(session, bucket):
    """(kind, prompt length, prefill bucket) of each slot's row: one LONG,
    up to CHECK_PROMPTS SHORT — at most half of the other slots —, the
    rest MID through the tenant's buckets in turn."""
    ladder, slots = session._seq_ladder, session._slots
    shorts = min(CHECK_PROMPTS, (slots - 1) // 2)
    plans = [(LONG, max(ladder) - LONG_SHORT, max(ladder))]
    plans += [(SHORT, min(CHECK_PROMPT_LEN, bucket - 1), bucket)] * shorts
    for i in range(slots - 1 - shorts):
        t = ladder[i % len(ladder)]
        plans.append((MID, max(t - MID_SHORT, t // 2), t))
    return plans


def check_rows(config, session, params, seed, bucket, control=None,
               steps=LONG_STEPS, fault=None):
    """The rows of the check the module's head describes, served and
    compared: a dict of arrays over all rows' compared positions — `err`
    (the largest logit difference as a share of the row's largest
    |reference logit|), `held` and `zero` (the reference router's two
    margins, each the least over the layers), `kind`, `position` — and
    `finite`, `prompts`, `buckets`.  `control`: a dtype in which the
    REFERENCE, on the sequences the program generated, stands in for the
    program's logits; `fault`: one of `reference.FAULTS` it then computes
    too."""
    rng = np.random.default_rng(seed)
    plans = check_plans(session, bucket)
    # (a tenant of short rings, the rehearsal's, steps as far as they go)
    steps = min(steps, session._max_len - max(n for _, n, _ in plans))
    prompts = [[int(t) for t in rng.integers(0, config["vocab_size"], n)]
               for _, n, _ in plans]
    slots = rng.permutation(session._slots)
    got, seqs = _serve_rows(session, prompts, [p[2] for p in plans], slots,
                            steps, config["vocab_size"])
    published = checkpoint_layout(params, config)
    out = {"err": [], "held": [], "zero": [], "kind": [], "position": []}
    for (kind, n, _), toks, mine in zip(plans, seqs, got):
        rows = list(range(n - 1, n + steps))
        ref, margins = reference.forward(published, config, toks, rows=rows)
        ref = np.asarray(ref, np.float64)
        if control is not None or fault is not None:
            mine = np.asarray(reference.forward(
                published, config, toks, rows=rows, dtype=control,
                fault=fault)[0], np.float32)
        out["err"].extend(np.abs(mine - ref).max(axis=-1)
                          / np.abs(ref).max(axis=-1))
        held, zero = np.asarray(margins).min(axis=0)[:, rows]  # over layers
        out["held"].extend(held)
        out["zero"].extend(zero)
        out["kind"].extend([kind] * len(rows))
        out["position"].extend(rows)
    out = {k: np.asarray(v) for k, v in out.items()}
    return dict(out, finite=bool(np.isfinite(got).all()),
                prompts=[p[1] for p in plans], buckets=[p[2] for p in plans])


def judge(rows, block, ring_len, router_rel_err, control=None):
    """(ok, facts) of `check_rows`' rows by the limits above; `block` /
    `ring_len`: the positions the decode program reads at a time, and a
    page's."""
    errs, kind = rows["err"], rows["kind"]
    clear = (rows["held"] >= NEAR_TIE_HELD) & (rows["zero"] >= NEAR_TIE_ZERO)
    at_crossing = ((rows["position"] >= block)
                   & (rows["position"] % block < CROSSING))
    # the counts are the cell's, of eight rows and LONG_STEPS steps (a
    # tenant of few slots, the tests' and the rehearsal's, has few rows),
    # and no row crosses anything where the program reads whole pages
    full = len(rows["prompts"]) >= 8 and len(errs) > 8 * LONG_STEPS
    min_long = MIN_LONG_COMPARED if full else 0
    min_crossing = MIN_CROSSING_COMPARED if full and block < ring_len else 0

    def stat(mask, reduce):
        return float(reduce(errs[mask])) if mask.any() else float("inf")

    def median(mask):
        return stat(mask, np.median)

    def of_kind(k):   # (a tenant of two slots has no SHORT row: 0, no fault)
        return median(clear & (kind == k)) if (kind == k).any() else 0.0

    facts = {"logit_rel_err": median(clear),
             "logit_rel_err_short": of_kind(SHORT),
             "logit_rel_err_mid": of_kind(MID),
             "logit_rel_err_long": of_kind(LONG),
             "logit_rel_err_crossing": (median(clear & at_crossing)
                                        if min_crossing else 0.0),
             "logit_rel_err_high": stat(
                 clear, lambda e: np.quantile(e, HIGH_QUANTILE)),
             "logit_rel_err_worst": stat(clear, np.max),
             "logit_rel_err_skipped": stat(~clear, np.max),
             "router_rel_err": router_rel_err,
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "skipped_held": int((rows["held"] < NEAR_TIE_HELD).sum()),
             "skipped_zero": int((rows["zero"] < NEAR_TIE_ZERO).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some layer the edge of the reference's "
             "choice lies within near_tie_held of a held expert's score, or "
             "within near_tie_zero of a zero-compute expert's across from a "
             "column that is none (shares of the last kept probability)",
             "rows_a_step": len(rows["prompts"]),
             "steps": int(len(errs) // len(rows["prompts"]) - 1),
             "prompts": rows["prompts"], "buckets": rows["buckets"],
             "long_compared": int((clear & (kind == LONG)).sum()),
             "crossing_compared": int((clear & at_crossing).sum()),
             "ring_block": int(block), "control": control,
             "limits": {"median": LOGIT_RTOL,
                        "q%d" % round(100 * HIGH_QUANTILE): LOGIT_RTOL_HIGH,
                        "worst": LOGIT_RTOL_WORST, "router": ROUTER_RTOL,
                        "near_tie_held": NEAR_TIE_HELD,
                        "near_tie_zero": NEAR_TIE_ZERO,
                        "min_compared_share": MIN_COMPARED_SHARE,
                        "min_long_compared": min_long,
                        "min_crossing_compared": min_crossing}}
    medians = [facts["logit_rel_err" + group]
               for group in ("", "_short", "_mid", "_long", "_crossing")]
    refused_by = [name for name, bad in (
        ("finite", not rows["finite"]),
        ("compared_share", facts["remaining_share"] < MIN_COMPARED_SHARE),
        ("long_compared", facts["long_compared"] < min_long),
        ("crossing_compared", facts["crossing_compared"] < min_crossing),
        ("median", max(medians) > LOGIT_RTOL),
        ("high", facts["logit_rel_err_high"] > LOGIT_RTOL_HIGH),
        ("worst", facts["logit_rel_err_worst"] > LOGIT_RTOL_WORST),
        ("router", router_rel_err > ROUTER_RTOL)) if bad]
    facts["refused_by"] = refused_by
    return not refused_by, facts


def check_against_reference(config, session, params, seed, bucket,
                            control=None, steps=LONG_STEPS, fault=None):
    """`check_rows` judged by the limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, bucket, control, steps,
                      fault)
    return judge(rows, int(session._ring_blocks.min()),
                 int(session._ring_lens.max()), router_error(params, config),
                 control or fault)


# ----------------------------------------------------------------------
# bytes and operations, for the hand rooflines (PERF.md section 5)
# ----------------------------------------------------------------------

def _mla_params(config):
    """Parameters of ONE attention sublayer's matrices, at the heads held."""
    d = config["hidden_size"]
    h, nope, rope, value, q_rank, kv_rank = _latent(config)
    return (d * q_rank + q_rank * h * (nope + rope) + d * (kv_rank + rope)
            + kv_rank * h * (nope + value) + h * value * d)


def _expert_params(config):
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]


def expected_experts_hit(config, rows):
    """Held experts hit by a step of `rows` rows under uniform routing."""
    held, wide = held_experts(config)[1], config["router_experts"]
    return held * (1.0 - (1.0 - config["moe_topk"] / wide) ** rows)


def step_bytes(config, rows, lengths, experts_hit, block=384):
    """Bytes ONE decode step of `rows` rows reads, by part, float32: every
    weight outside the routed experts once (two attention sublayers, two
    dense FFNs and the router a published layer), `experts_hit` (a layer)
    of the held experts' matrices, the head, and both latent pages of
    every published layer as far as the kernel's block that holds each
    row's `length`."""
    d, v = config["hidden_size"], config["vocab_size"]
    layers = config["num_layers"]
    line = 4 * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    return {"mla": 4 * layers * 2 * _mla_params(config),
            "dense_ffn": 4 * layers * 2 * 3 * d * config["ffn_hidden_size"],
            "router": 4 * layers * d * config["router_experts"],
            "experts": 4 * layers * experts_hit * _expert_params(config),
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "ring": 2 * layers * sum(line * (n // block + 1) * block
                                     for n in lengths)}


def prefill_flops(config, positions):
    """Multiply-adds x 2 of ONE prefill of a bucket of `positions`, by
    part: the attention sublayers' projections, their scores and context
    over the causal half (the key 192 wide, the value carried at it), the
    dense FFNs, the router, the pairs that land on held experts under
    uniform routing, the head's one row."""
    d = config["hidden_size"]
    h, nope, rope, _, _, _ = _latent(config)
    layers = config["num_layers"]
    held_share = held_experts(config)[1] / config["router_experts"]
    return {"mla": 2 * layers * 2 * positions * _mla_params(config),
            "attention": 2 * layers * 2 * h * positions * positions
            * 2 * (nope + rope) // 2,
            "dense_ffn": 2 * layers * 2 * positions * 3 * d
            * config["ffn_hidden_size"],
            "router": 2 * layers * positions * d * config["router_experts"],
            "experts": 2 * layers * positions * config["moe_topk"]
            * held_share * _expert_params(config),
            "head": 2 * d * config["vocab_size"]}
