"""The `mistral4` family: how a configuration file becomes the model under
test (`models.TransformerLM` with Mistral-Small-4's block: multi-head
latent attention — a query of rank 1,024, ONE cached row of 256 + 64 a
position for all 32 heads, YaRN on the decoupled rotary part — and in
EVERY layer 4 of 128 softmax-routed experts of width 2,048 beside an
ungated shared one — held as ONE CHIP'S SHARE of the deployment the file
states), its seeded weights, its comparison with the plain reference, and
the bytes and operations of its decode step."""
import numpy as np

from ..reference import mistral4 as reference
from .afmoe import INIT_STD, _no_chip_favoured, router_error as _router_error
from .qwen3_next import _serve_rows

# Router columns N(0, ROUTER_LOGIT_STD / sqrt(hidden size)) — 0.02 at the
# published 4,096: the normed stream has unit RMS, so router logits have a
# standard deviation of about 1.3 at ANY width (the rehearsal's router
# then has the near ties of the cell's, no more); the four largest of 128
# then run over ~0.5 in logit and the renormalised weights from ~0.19 to
# ~0.33 — visibly different, so weights that are not renormalised (they
# would sum to ~0.15) or a softmax over the four alone show.  WHICH
# experts a token picks stays near uniform, so load, experts hit and bytes
# read are those of a balanced trained router.
ROUTER_LOGIT_STD = 1.28

# THE CHECK, through the timed tenant's own programs and ring, with EVERY
# SLOT LIVE (as `families/qwen3_next.py`, whose `_serve_rows` this uses):
# one prompt a slot, each prefilled alone through the prefill program of
# its bucket — the UP-PROJECTED form, whose 320-wide rows fill the latent
# ring — then LONG_STEPS greedy decode steps of ALL rows at once through
# the decode program of as many rows as the tenant has slots — the ABSORBED
# form over the ring, at 16 slots the 16-row step that is ~96% of the
# cell's window.  Every row's logits (over the vocabulary slice), of its
# prefill and of each step, against ONE blocked float32 forward of the
# reference over that row's final sequence.  The rows, in slots drawn from
# the seed:
#
# (a) CHECK_PROMPTS short prompts (fewer where the tenant has few slots)
#     of CHECK_PROMPT_LEN through the SMALLEST bucket: contexts of 24-344;
# (b) ONE prompt LONG_SHORT short of the largest bucket through that
#     bucket — at 2,048: a page filled to 2,040, the kernel's third block
#     of 768, and past 2,304 into its fourth at the 264th step;
# (c) in every other slot a prompt of MID_SHARE of the smallest bucket —
#     608 at 768, so its steps run over 608-928 and cross the kernel's
#     first block boundary, 768, half way — through the tenant's buckets
#     in turn, so that every prefill program hands a ring filled behind
#     160-1,440 positions of pad to the step.
CHECK_PROMPTS = 4
CHECK_PROMPT_LEN = 24
LONG_SHORT = 8
LONG_STEPS = 320
MID_SHARE = 19 / 24
SHORT, MID, LONG = 0, 1, 2
# A compared row is AT A CROSSING where its position lies in the first
# CROSSING positions of a block — of the session's own count of what its
# decode program reads at a time: 768 of the ring's 6,144 on the TPU, the
# whole page off it — after the first: the kernel has just begun to read
# one block more.
CROSSING = 16
# Rows where the reference's router has a near tie THAT THIS CHIP FEELS in
# any of the layers are counted and skipped, as OLMoE's, Trinity's and
# Qwen3-Next's are and for their reason: the model under test multiplies
# its projections at one bfloat16 pass, its normed stream differs from the
# reference's by a part in fifty and a router logit by as much (the router
# itself is float32 at "highest" on both sides), and where two candidates
# lie closer than that the two sides keep different experts: another
# rounding of the same model, not a fault.  Here a token keeps FOUR
# experts with weights of a fifth to a third each, so one changed choice
# moves a row's logits by 10-50% (Qwen3-Next keeps ten of small weight:
# 4-5%), and every row with one has to go before the worst row says
# anything.  The margin is `reference.route`'s: the least distance of a
# HELD expert's probability from the edge of the choice, as a share of the
# fourth probability — a tie between two experts of other chips changes no
# term this chip computes.  Readings (my chip runs, PR 44, three seeds,
# 15,408 rows): rows over 8% per margin bin of 0.01 from 0: 365, 186, 65,
# 33, 8, 6, 3, 1 and NONE in the 8,636 rows from 0.08 on (the largest
# there 3.2%); the bfloat16 control, whose error is 1.7 times the
# program's, has them up to 0.124.  The count falls to ~0.45 of itself a
# bin, so a margin of 0.08 would meet one every few runs and 0.15 one in
# about a thousand; 0.15 keeps 31-32% of the rows, 1,615-1,651.
NEAR_TIE = 0.15
# LIMITS, each a share of the row's largest |reference logit|; the
# readings are at the end of this comment and in PERF.md section 6.
#   LOGIT_RTOL        the MEDIAN of the compared rows — of all, and of the
#                     short, the mid, the long rows and those at a crossing
#                     by themselves, the largest of the five: what is wrong
#                     in every row, or in every row of one kind (a gain, a
#                     norm, sigma, the rotary's frequencies or layout, the
#                     absorbed products, a lower precision; the ring a
#                     prefill program hands over; the block the kernel has
#                     just begun to read).
#   LOGIT_RTOL_HIGH   the HIGH_QUANTILE of all compared rows: a fault in a
#                     tenth of the rows or more.
#   LOGIT_RTOL_WORST  the WORST compared row: one row wrong — a row that
#                     read another slot's page, stale memory behind a
#                     block's edge.
# Readings (my chip runs, PR 44, TPU v5e; three probed seeds and thirteen
# runs of the cell, at NEAR_TIE 0.15): SOUND — medians' largest 1.91-1.99%
# (the short rows'; all rows 1.82-1.85%), 0.9 quantile 2.17-2.21%, worst
# 2.85-3.74% (thirteen of sixteen under 3.2%); CONTROL, the reference with
# weights and activations in bfloat16 in the program's place ON THE SAME
# SEQUENCES (`control="bfloat16"`, through this same comparison, two
# seeds): 3.19-3.20% / 3.74-3.76% / 5.11-5.17%, refused by each of the
# three limits on both.  LOGIT_RTOL and LOGIT_RTOL_HIGH are the geometric
# middles of the sound side's largest and the control's smallest;
# LOGIT_RTOL_WORST, the largest of 1,600 rows and so the least steady
# reading, stands a fifth over the sound side's largest and an eighth
# under the control's smallest.
LOGIT_RTOL = 2.5e-2
LOGIT_RTOL_HIGH = 2.9e-2
LOGIT_RTOL_WORST = 4.5e-2
HIGH_QUANTILE = 0.9
# compared rows of (b) and at a crossing (where the program reads by
# blocks): 90-115 and 47-75 were read over sixteen seeds
MIN_LONG_COMPARED = 48
MIN_CROSSING_COMPARED = 24
# the program's own router function against the float32 product at
# "highest" (families/afmoe.py `router_error` says why it is checked
# where it is stated): float32 at "highest" reads ~1e-6, one bfloat16
# pass 2e-3
ROUTER_RTOL = 1e-4


def held_experts(config):
    """(first, count) of the routed experts this chip holds."""
    first, count = config["held_experts"]
    assert count == config["n_routed_experts"]
    return int(first), int(count)


def model_args(config):
    """`TransformerLM`'s arguments for this configuration."""
    layers = config["num_hidden_layers"]
    rope, assumed = config["rope_parameters"], config["assumed"]
    assert config["first_k_dense_replace"] == 0 and config["rope_interleave"]
    assert config["n_group"] == config["topk_group"] == 1
    assert assumed["router"]["scoring_func"] in ("softmax", "sigmoid")
    assert not assumed["router"]["selection_bias"]
    assert config["n_shared_experts"] == 1
    return dict(
        vocab=config["vocab_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="none",
        rope_theta=rope["rope_theta"], rope_scaling=rope,
        attention_multiplier=assumed["softmax_scale"]["value"],
        query_scale=((rope["llama_4_scaling_beta"],
                      rope["original_max_position_embeddings"])
                     if assumed["query_scale"]["applied"] else None),
        bias=False, tied_head=config["tie_word_embeddings"], ffn="swiglu",
        layer_types=["latent_attention"] * layers,
        latent_q_rank=config["q_lora_rank"],
        latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"],
        latent_rope_dim=config["qk_rope_head_dim"],
        latent_value_dim=config["v_head_dim"],
        ffn_types=["routed"] * layers,
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        shared_d_ff=(config["n_shared_experts"]
                     * config["moe_intermediate_size"]),
        router_score=assumed["router"]["scoring_func"],
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def _latent(config):
    """(heads, nope, rope, value, q rank, kv rank) of the attention."""
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["q_lora_rank"], config["kv_lora_rank"])


def param_shapes(config):
    d, v = config["hidden_size"], config["vocab_size"]
    h, nope, rope, value, q_rank, kv_rank = _latent(config)
    xf = config["moe_intermediate_size"]
    sf = config["n_shared_experts"] * xf
    total, held = config["router_experts"], held_experts(config)[1]
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    layer = {"ln1_gamma": (d,), "qa_weight": (q_rank, d),
             "qa_norm_gamma": (q_rank,),
             "qb_weight": (h * (nope + rope), q_rank),
             "kva_weight": (kv_rank + rope, d),
             "kva_norm_gamma": (kv_rank,),
             "kvb_weight": (h * (nope + value), kv_rank),
             "out_weight": (d, h * value), "ln2_gamma": (d,),
             "router_weight": (d, total), "gate_weight": (held, d, xf),
             "up_weight": (held, d, xf), "down_weight": (held, xf, d),
             "shared_gate_weight": (d, sf), "shared_up_weight": (d, sf),
             "shared_down_weight": (sf, d)}
    for i in range(config["num_hidden_layers"]):
        for n, s in layer.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in and in the PROGRAM'S layout (`checkpoint_layout` turns them
    to the published one): matrices and embeddings N(0, INIT_STD); every
    gain 1 + N(0, 0.1), so that a norm that is dropped or crossed shows;
    the router N(0, ROUTER_LOGIT_STD / sqrt(d)), each chip's columns
    summing to zero so that the draw favours no chip
    (`afmoe._no_chip_favoured`).  One jitted call a tensor (one program a
    shape)."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 7.8 GB of weights are made
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
            gain, router = (name.endswith("_gamma"),
                            name.endswith("_router_weight"))
            out[name] = normal(jax.random.fold_in(key, i), float(gain),
                               0.1 if gain else router_std if router
                               else INIT_STD, shape)
            if router:
                out[name] = _no_chip_favoured(out[name],
                                              held_experts(config)[1])
    return out


def layout_rows(config):
    """(rows of `W_qb`, rows of `W_kva`): for each row of the PROGRAM'S
    matrix, the row of the published checkpoint's it holds.  The program
    keeps `W_qb` by kind — all heads' q_nope, then all heads' q_rope — and
    a rotary part's channels in the rotate-half order, the first of every
    pair and then the second of every pair; the checkpoint keeps each
    head's ``[q_nope | q_rope]`` together and the pairs interleaved."""
    h, nope, rope, _, _, kv_rank = _latent(config)
    half = np.arange(rope) // (rope // 2)           # 0: firsts, 1: seconds
    turned = 2 * (np.arange(rope) % (rope // 2)) + half
    head = np.arange(h)[:, None] * (nope + rope)
    qb = np.concatenate([(head + np.arange(nope)).ravel(),
                         (head + nope + turned).ravel()])
    kva = np.concatenate([np.arange(kv_rank), kv_rank + turned])
    return qb, kva


def checkpoint_layout(params, config):
    """`params` with every `W_qb` and `W_kva` as the published checkpoint
    lays them out (what `reference/mistral4.py` takes): the inverse of
    `layout_rows`' permutation of their rows."""
    qb, kva = (np.argsort(rows) for rows in layout_rows(config))
    return {name: value[qb] if name.endswith("_qb_weight")
            else value[kva] if name.endswith("_kva_weight") else value
            for name, value in params.items()}


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
    plans += [(MID, int(bucket * MID_SHARE), ladder[i % len(ladder)])
              for i in range(slots - 1 - shorts)]
    return plans


def check_rows(config, session, params, seed, bucket, control=None,
               steps=LONG_STEPS):
    """The rows of the check the module's head describes, served and
    compared: a dict of arrays over all rows' compared positions — `err`
    (the largest logit difference as a share of the row's largest
    |reference logit|), `margin` (the reference router's over the held
    experts, the least over the layers), `kind`, `position` — and `finite`, `prompts`, `buckets`.
    `control`: a dtype in which the REFERENCE, on the sequences the
    program generated, stands in for the program's logits."""
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
    out = {"err": [], "margin": [], "kind": [], "position": []}
    for (kind, n, _), toks, mine in zip(plans, seqs, got):
        rows = list(range(n - 1, n + steps))
        ref, margin = reference.forward(published, config, toks, rows=rows)
        ref = np.asarray(ref, np.float64)
        if control is not None:
            mine = np.asarray(reference.forward(
                published, config, toks, rows=rows, dtype=control)[0],
                np.float32)
        out["err"].extend(np.abs(mine - ref).max(axis=-1)
                          / np.abs(ref).max(axis=-1))
        # over the layers
        out["margin"].extend(np.asarray(margin).min(axis=0)[rows])
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
    clear = rows["margin"] >= NEAR_TIE
    at_crossing = ((rows["position"] >= block)
                   & (rows["position"] % block < CROSSING))
    # the counts are the cell's, of sixteen rows and LONG_STEPS steps (a
    # tenant of few slots, the tests' and the rehearsal's, has few rows),
    # and no row crosses anything where the program reads whole pages
    full = len(rows["prompts"]) >= 16 and len(errs) > 16 * LONG_STEPS
    min_long = MIN_LONG_COMPARED if full else 0
    min_crossing = MIN_CROSSING_COMPARED if full and block < ring_len else 0

    def stat(mask, reduce):
        return float(reduce(errs[mask])) if mask.any() else float("inf")

    def median(mask):
        return stat(mask, np.median)

    facts = {"logit_rel_err": median(clear),
             "logit_rel_err_short": median(clear & (kind == SHORT)),
             "logit_rel_err_mid": median(clear & (kind == MID)),
             "logit_rel_err_long": median(clear & (kind == LONG)),
             "logit_rel_err_crossing": (median(clear & at_crossing)
                                        if min_crossing else 0.0),
             "logit_rel_err_high": stat(
                 clear, lambda e: np.quantile(e, HIGH_QUANTILE)),
             "logit_rel_err_worst": stat(clear, np.max),
             "logit_rel_err_skipped": stat(~clear, np.max),
             "router_rel_err": router_rel_err,
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some layer a held expert's router "
             "probability lies closer to the edge of the reference's choice "
             "than near_tie of the last kept one",
             "rows_a_step": len(rows["prompts"]),
             "steps": int(len(errs) // len(rows["prompts"]) - 1),
             "prompts": rows["prompts"], "buckets": rows["buckets"],
             "long_compared": int((clear & (kind == LONG)).sum()),
             "crossing_compared": int((clear & at_crossing).sum()),
             "ring_block": int(block), "control": control,
             "limits": {"median": LOGIT_RTOL,
                        "q%d" % round(100 * HIGH_QUANTILE): LOGIT_RTOL_HIGH,
                        "worst": LOGIT_RTOL_WORST, "router": ROUTER_RTOL,
                        "near_tie": NEAR_TIE,
                        "min_long_compared": min_long,
                        "min_crossing_compared": min_crossing}}
    medians = [facts["logit_rel_err" + group]
               for group in ("", "_short", "_mid", "_long", "_crossing")]
    ok = (rows["finite"]
          and facts["long_compared"] >= min_long
          and facts["crossing_compared"] >= min_crossing
          and max(medians) <= LOGIT_RTOL
          and facts["logit_rel_err_high"] <= LOGIT_RTOL_HIGH
          and facts["logit_rel_err_worst"] <= LOGIT_RTOL_WORST
          and router_rel_err <= ROUTER_RTOL)
    return bool(ok), facts


def check_against_reference(config, session, params, seed, bucket,
                            control=None, steps=LONG_STEPS):
    """`check_rows` judged by the limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, bucket, control, steps)
    return judge(rows, int(session._ring_blocks.min()),
                 int(session._ring_lens.max()), router_error(params, config),
                 control)


# ----------------------------------------------------------------------
# bytes and operations, for the hand rooflines (PERF.md section 5)
# ----------------------------------------------------------------------

def _mla_params(config):
    """Parameters of one layer's attention matrices."""
    d = config["hidden_size"]
    h, nope, rope, value, q_rank, kv_rank = _latent(config)
    return (d * q_rank + q_rank * h * (nope + rope) + d * (kv_rank + rope)
            + kv_rank * h * (nope + value) + h * value * d)


def expert_bytes(config, experts_hit):
    """ONE layer's routed experts' matrices a step reads: `experts_hit`
    of the held ones, three matrices of ``d x f`` each, float32."""
    return 4 * experts_hit * 3 * (config["hidden_size"]
                                  * config["moe_intermediate_size"])


def expected_experts_hit(config, rows):
    """Held experts hit by a step of `rows` rows under uniform routing."""
    held, total = held_experts(config)[1], config["router_experts"]
    k = config["num_experts_per_tok"]
    return held * (1.0 - (1.0 - k / total) ** rows)


def ring_bytes(config, lengths, block=768, ring_len=None):
    """The latent pages a decode step reads for rows at `lengths`, all
    layers: each row's ONE page of ``kv_lora_rank + qk_rope_head_dim``
    float32 lines, as far as the kernel's block that holds `length` (at
    most the ring's `ring_len` positions) — read once, for scores and
    context alike."""
    line = 4 * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    cap = float("inf") if ring_len is None else ring_len
    return config["num_hidden_layers"] * sum(
        line * min((n // block + 1) * block, cap) for n in lengths)


def step_bytes(config, rows, lengths, experts_hit, block=768):
    """Bytes ONE decode step of `rows` rows reads, by part: every weight
    outside the routed experts once, `experts_hit` (a layer) of the held
    experts' matrices, the latent pages as far as they are filled."""
    d, v = config["hidden_size"], config["vocab_size"]
    layers = config["num_hidden_layers"]
    shared = (config["n_shared_experts"] * 3 * d
              * config["moe_intermediate_size"]
              + d * config["router_experts"])
    return {"mla": 4 * layers * _mla_params(config),
            "shared_and_router": 4 * layers * shared,
            "experts": layers * expert_bytes(config, experts_hit),
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "ring": ring_bytes(config, lengths, block)}


def step_flops(config, rows, lengths, block=768):
    """Multiply-adds x 2 of ONE decode step of `rows` rows at `lengths`,
    by part: the attention's projections with the two absorbed products,
    the kernel's scores and context over the blocks it reads (all heads
    against the ``rank + rope`` lines, then the first `rank`), the router
    and the shared expert, the routed pairs that land on held experts
    under uniform routing, the head."""
    d = config["hidden_size"]
    h, nope, rope, value, _, kv_rank = _latent(config)
    layers = config["num_hidden_layers"]
    expert = 3 * d * config["moe_intermediate_size"]
    held_share = held_experts(config)[1] / config["router_experts"]
    read = sum((n // block + 1) * block for n in lengths)
    return {"mla": 2 * layers * rows * _mla_params(config),
            "ring": 2 * layers * h * read * (2 * kv_rank + rope),
            "shared_and_router": 2 * layers * rows * (
                config["n_shared_experts"] * expert
                + d * config["router_experts"]),
            "experts": 2 * layers * rows * config["num_experts_per_tok"]
            * held_share * expert,
            "head": 2 * rows * d * config["vocab_size"]}
