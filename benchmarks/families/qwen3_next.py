"""The `qwen3_next` family: how a configuration file becomes the model
under test (`models.TransformerLM` with Qwen3-Next's block: three Gated
DeltaNet layers — 16 heads of q and k under 32 value heads of 128 x 128 —
to one gated attention of 16 query heads of 256 over 2 K/V heads with a
quarter of each head rotated, per-head QK-norm, input norms only, and in
EVERY layer 10 of 512 softmax-routed experts of width 512 beside a shared
expert with a sigmoid gate of its own — held as ONE CHIP'S SHARE of the
deployment the file states), its seeded weights, its comparison with the
plain reference, and the bytes and operations of its programs."""
import numpy as np

from ..reference import qwen3_next as reference
from .afmoe import INIT_STD, _no_chip_favoured, router_error as _router_error
from .olmo_hybrid import A_RANGE, CONV_BOUND, DT_RANGE

# Router columns N(0, 0.02): the normed stream has unit RMS over 2,048
# channels, so router logits have a standard deviation of about 0.9; the
# ten largest of 512 then run over ~0.75 in logit, their probabilities
# over a factor of two, and the renormalised weights from ~0.07 to ~0.15 —
# visibly different, so weights that are not renormalised (they would sum
# to ~0.05) or a softmax taken over the ten alone show.  WHICH experts a
# token picks stays near uniform, so load, experts hit and bytes read are
# those of a balanced trained router.
ROUTER_STD = 0.02
CHUNK = 64   # of the delta rule's chunked form: no key of config.json

# THE CHECK, through the timed tenant's own programs and state, with EVERY
# SLOT LIVE: one prompt a slot, each prefilled alone through the prefill
# program of its bucket (as the batcher prefills), then LONG_STEPS greedy
# decode steps of ALL rows at once through the decode program of as many
# rows as the tenant has slots — at 16 slots the 16-row step that is ~89%
# of the cell's window.  Every row's logits (over the vocabulary slice), of
# its prefill and of each step, against ONE blocked float32 forward of the
# reference over that row's final sequence.  The rows, in slots drawn from
# the seed (a row's place in the step is not its slot):
#
# (a) CHECK_PROMPTS short prompts (fewer where the tenant has few slots)
#     of CHECK_PROMPT_LEN through the SMALLEST bucket: contexts of 24-152;
# (b) ONE prompt LONG_SHORT short of the largest bucket through that
#     bucket — at 2,048: the kernel's 32 chunks of 64, the last one 8
#     positions of pad, the conv window and the final state of each
#     delta-rule layer handed to the step, the full layer's 256-wide ring
#     filled to its fourth block of 512 and past it at the eighth step;
# (c) in every other slot a prompt of MID_SHARE of the smallest bucket —
#     448 at 768, so its steps run over 448-576 and cross the ring
#     kernel's first block boundary, 512, half way — through the
#     tenant's buckets in turn, so that every prefill program hands a
#     state and a ring filled behind 320-1,600 positions of pad to the
#     step.
CHECK_PROMPTS = 4
CHECK_PROMPT_LEN = 24
LONG_SHORT = 8
LONG_STEPS = 128
MID_SHARE = 7 / 12
SHORT, MID, LONG = 0, 1, 2
# A compared row is AT A CROSSING where its position lies in the first
# CROSSING positions of a block — of the session's own count of what its
# decode program's attention reads at a time: 512 of the full layer's
# 4,096 on the TPU, the whole page off it — after the first: the kernel
# has just begun to read one block more.
CROSSING = 16
# Rows where the reference's router has a near tie in ANY of the layers —
# the tenth and eleventh probability closer than NEAR_TIE of the tenth —
# are counted and skipped, as OLMoE's and Trinity's are and for their
# reason: the model under test multiplies its projections at one bfloat16
# pass, its normed stream differs from the reference's by a few parts in a
# thousand and a router logit by as much (the router itself is float32 at
# "highest" on both sides), and where two candidates lie closer than that
# the two sides keep different experts: another rounding of the same
# model, not a fault.  Of 512 logits of deviation ~0.9 the tenth and
# eleventh lie ~0.036 apart in the mean, so a margin of 0.01 skips a
# quarter of the rows a layer and about two thirds over four layers.
NEAR_TIE = 1e-2
# LIMITS, each a share of the row's largest |reference logit|.  Readings
# (my chip runs, PR 40, second session, TPU v5e; PERF.md section 6 lists
# them): SOUND, the cell's tenant on twenty-two seeds (six probed,
# sixteen runs of the cell, fifteen of them the committed files), 676-752
# of the 2,064 rows compared, 30-54 of (b)'s 129 and 54-77 at a crossing;
# CONTROL, the reference with weights, activations and state in bfloat16
# in the program's place ON THE SAME SEQUENCES the program had generated
# (`control="bfloat16"`, through this same comparison: refused on all six
# seeds, by each of the first two limits); FAULT, the weakest of the
# seeded faults at the cell's own size, the rotary over the whole head
# (refused by all three).
#   LOGIT_RTOL        the MEDIAN of the compared rows — of all, and of the
#                     short, the mid, the long rows and those at a crossing
#                     by themselves, the largest of the five: what is
#                     wrong in every row, or in every row of one kind (a
#                     gain, a gate, the rotary, the weights' norm, the
#                     head mapping, a lower precision; the state the
#                     kernel hands over; the block the ring kernel has
#                     just begun to read).  Sound 1.41-1.91% (over all
#                     rows 1.56-1.72), control 3.45-4.52% (over all rows
#                     3.56-4.19), fault 6.93%; the geometric middle of the
#                     first seven's 1.75 and 3.45 (the later 1.91, 76
#                     rows at a crossing, moves it to 2.57: left as it
#                     was).  The 16-row step reads what the
#                     prefill programs read (their sixteen rows a seed:
#                     1.3-4.2%); the ONE-row decode program the first
#                     session's check went through read a third of it
#                     (0.31-1.08% over thirty-five seeds): XLA does not
#                     put a one-row product on the MXU (compiled for a
#                     described v5e it is a multiply-and-reduce fusion in
#                     float32, from two rows on a convolution:
#                     tests/test_tpu_compile.py), so that program
#                     multiplied in more than the stated one bfloat16
#                     pass, and its limits (1.8 / 3.0 / 1.8%) were set on
#                     a program the window hardly runs.
#   LOGIT_RTOL_HIGH   the HIGH_QUANTILE of all compared rows: a fault in a
#                     tenth of the rows or more.  Sound 2.30-2.58%, control
#                     4.49-5.11%, fault 7.23%; the geometric middle of
#                     2.58 and 4.49.
#   LOGIT_RTOL_WORST  the WORST compared row: one row wrong — a row that
#                     read another slot's page or state, stale memory
#                     behind a segment (PR 38's fault read 36%).  Sound
#                     3.72-5.48% (the 5.48 a row whose margin was 0.0107,
#                     just clear of NEAR_TIE: of the probes' 4,291
#                     compared rows 11 read over 4%, one over 5%, a
#                     twelfth as many for each point more; the twenty-two
#                     worsts fit a Gumbel law of location 4.14, scale
#                     0.37, by which one run in thirty thousand passes
#                     8%; the skipped rows reach 7.10%), fault 11.3%; the
#                     geometric middle.  The control reads 5.74-7.35% and
#                     is not what this limit is for.
LOGIT_RTOL = 2.5e-2
LOGIT_RTOL_HIGH = 3.4e-2
LOGIT_RTOL_WORST = 8.0e-2
HIGH_QUANTILE = 0.9
# compared rows of (b), 30-54 measured, and at a crossing (where the
# program reads by blocks), 54-77 measured
MIN_LONG_COMPARED = 16
MIN_CROSSING_COMPARED = 16
# the program's own router function against the float32 product at
# "highest" (families/afmoe.py `router_error` says why it is checked
# where it is stated): float32 at "highest" reads ~1e-6, one bfloat16
# pass 2e-3
ROUTER_RTOL = 1e-4


def held_experts(config):
    """(first, count) of the routed experts this chip holds."""
    first, count = config["held_experts"]
    assert count == config["num_experts"]
    return int(first), int(count)


def layer_kinds(config):
    """The model-under-test kind of each layer: every
    `full_attention_interval`-th is full attention."""
    every = config["full_attention_interval"]
    return ["linear_attention" if (i + 1) % every else "attention"
            for i in range(config["num_hidden_layers"])]


def model_args(config):
    """`TransformerLM`'s arguments for this configuration."""
    layers = config["num_hidden_layers"]
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    return dict(
        vocab=config["vocab_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="rotary",
        rope_theta=config["rope_theta"],
        rotary_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        qk_norm="head", out_gate=True, bias=False,
        tied_head=config["tie_word_embeddings"], ffn="swiglu",
        layer_types=layer_kinds(config), ffn_types=["routed"] * layers,
        linear_heads=config["linear_num_value_heads"],
        linear_key_heads=config["linear_num_key_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        linear_conv=config["linear_conv_kernel_dim"], linear_chunk=CHUNK,
        linear_neg_eigval=False,
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        shared_d_ff=config["shared_expert_intermediate_size"],
        shared_gate=True, route_norm=config["norm_topk_prob"],
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def _linear(config):
    """(q/k heads, value heads, d_k, d_v, conv channels, projection rows)
    of a delta-rule layer."""
    hk, h = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    conv_dim = 2 * hk * dk + h * dv
    return hk, h, dk, dv, conv_dim, conv_dim + h * dv + 2 * h


def param_shapes(config):
    d, v = config["hidden_size"], config["vocab_size"]
    dh = config["head_dim"]
    qw = config["num_attention_heads"] * dh
    kw = config["num_key_value_heads"] * dh
    xf, sf = (config["moe_intermediate_size"],
              config["shared_expert_intermediate_size"])
    total, held = config["router_experts"], held_experts(config)[1]
    _, h, _, dv, conv_dim, d_proj = _linear(config)
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    mixers = {
        "linear_attention": {
            "inproj_weight": (d_proj, d),
            "conv_weight": (config["linear_conv_kernel_dim"], conv_dim),
            "dt_bias": (h,), "A_log": (h,), "gnorm_gamma": (dv,),
            "outproj_weight": (d, h * dv)},
        "attention": {"qkv_weight": (2 * qw + 2 * kw, d),
                      "qnorm_gamma": (dh,), "knorm_gamma": (dh,),
                      "out_weight": (d, qw)}}
    routed = {"ln1_gamma": (d,), "ln2_gamma": (d,),
              "router_weight": (d, total),
              "gate_weight": (held, d, xf), "up_weight": (held, d, xf),
              "down_weight": (held, xf, d), "shared_gate_weight": (d, sf),
              "shared_up_weight": (d, sf), "shared_down_weight": (sf, d),
              "shared_score_weight": (d, 1)}
    for i, kind in enumerate(layer_kinds(config)):
        for n, s in {**routed, **mixers[kind]}.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in, drawn as `families/olmo_hybrid.py` draws a delta-rule
    model's and `families/afmoe.py` a routed one's: matrices and
    embeddings N(0, INIT_STD); every gain as the program stores it, ``1 +
    w`` with the published ``w`` ~ N(0, 0.1) (the delta rule's output
    norm's plain gain 1 + N(0, 0.1) too), so that one dropped, crossed or
    applied as ``w`` shows; ``A_log`` = log U(A_RANGE), ``dt_bias`` the
    inverse softplus of a log-uniform DT_RANGE, the conv's taps
    U(+-CONV_BOUND); the router N(0, ROUTER_STD), favouring no chip
    (`afmoe._no_chip_favoured`).  One jitted call a tensor."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 7.6 GB of weights are made
    model(config)
    dtype = jnp.dtype(config["param_dtype"])

    @functools.partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        if kind in ("matrix", "router"):
            std = ROUTER_STD if kind == "router" else INIT_STD
            return std * jax.random.normal(key, shape, dtype)
        if kind == "gain":
            return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
        if kind == "conv":
            return jax.random.uniform(key, shape, dtype, -CONV_BOUND,
                                      CONV_BOUND)
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))
        dt = jnp.exp(jax.random.uniform(key, shape, dtype,
                                        *np.log(DT_RANGE)))
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)

    kinds = {"gamma": "gain", "conv_weight": "conv", "A_log": "A_log",
             "dt_bias": "dt_bias", "router_weight": "router"}
    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            tail = name.split("_", 1)[1]
            kind = kinds.get(tail, kinds.get(tail.rsplit("_", 1)[-1], "matrix"))
            out[name] = draw(jax.random.fold_in(key, i), kind, shape)
            if kind == "router":
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
    plans += [(MID, int(bucket * MID_SHARE), ladder[i % len(ladder)])
              for i in range(slots - 1 - shorts)]
    return plans


def _serve_rows(session, prompts, buckets, slots, steps, vocab):
    """Row r's prompt prefilled ALONE into ``slots[r]`` through the prefill
    program of ``buckets[r]``, then `steps` greedy decode steps of ALL the
    rows in ONE call each, through the decode program of as many rows (the
    ladder's last, the slots full): (logits ``(rows, 1 + steps, vocab)``,
    each row's tokens).  The caller guarantees the batcher is idle and the
    slots free."""
    rows = len(prompts)
    toks = [list(p) for p in prompts]
    got = np.zeros((rows, 1 + steps, vocab), np.float32)
    for r, (prompt, t) in enumerate(zip(prompts, buckets)):
        exe, fn = session._program(session._prefill_pred, 1, t, True)
        data = np.zeros((1, t), np.float32)
        data[0, :len(prompt)] = prompt
        got[r, 0] = session._run(
            exe, fn, data, np.full((1,), slots[r], np.float32),
            np.full((1,), len(prompt), np.float32))[0]
    assert rows == session._decode_ladder[-1]
    exe, fn = session._program(session._decode_pred, rows, 1, False)
    slot = np.asarray(slots, np.float32)
    for step in range(steps):
        tokens = got[:, step].argmax(axis=-1)
        length = np.asarray([len(t) for t in toks], np.float32)
        for t, token in zip(toks, tokens):
            t.append(int(token))
        got[:, step + 1] = session._run(
            exe, fn, tokens[:, None].astype(np.float32), slot, length)
    return got, toks


def check_rows(config, session, params, seed, bucket, control=None):
    """The rows of the check the module's head describes, served and
    compared: a dict of arrays over all rows' compared positions — `err`
    (the largest logit difference as a share of the row's largest
    |reference logit|), `margin` (the reference router's, the least over
    the layers), `kind`, `position` — and `finite`, `prompts`, `buckets`.
    `control`: a dtype in which the REFERENCE, on the sequences the
    program generated, stands in for the program's logits."""
    rng = np.random.default_rng(seed)
    plans = check_plans(session, bucket)
    prompts = [[int(t) for t in rng.integers(0, config["vocab_size"], n)]
               for _, n, _ in plans]
    slots = rng.permutation(session._slots)
    got, seqs = _serve_rows(session, prompts, [p[2] for p in plans], slots,
                            LONG_STEPS, config["vocab_size"])
    out = {"err": [], "margin": [], "kind": [], "position": []}
    for (kind, n, _), toks, mine in zip(plans, seqs, got):
        rows = list(range(n - 1, n + LONG_STEPS))
        ref, margin = reference.forward(params, config, toks, rows=rows)
        ref = np.asarray(ref, np.float64)
        if control is not None:
            mine = np.asarray(reference.forward(
                params, config, toks, rows=rows, dtype=control)[0],
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


def check_against_reference(config, session, params, seed, bucket,
                            control=None):
    """`check_rows` judged by the limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, bucket, control)
    errs, kind = rows["err"], rows["kind"]
    clear = rows["margin"] >= NEAR_TIE
    block = int(session._ring_blocks.min())
    at_crossing = ((rows["position"] >= block)
                   & (rows["position"] % block < CROSSING))
    # no row crosses anything where the program reads whole pages
    min_crossing = (MIN_CROSSING_COMPARED
                    if block < int(session._ring_lens.max()) else 0)

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
             "router_rel_err": router_error(params, config),
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some layer the reference's last kept and "
             "first left-out router probability lie closer than near_tie of "
             "the former",
             "rows_a_step": len(rows["prompts"]), "steps": LONG_STEPS,
             "prompts": rows["prompts"], "buckets": rows["buckets"],
             "long_compared": int((clear & (kind == LONG)).sum()),
             "crossing_compared": int((clear & at_crossing).sum()),
             "ring_block": block, "control": control,
             "limits": {"median": LOGIT_RTOL,
                        "q%d" % round(100 * HIGH_QUANTILE): LOGIT_RTOL_HIGH,
                        "worst": LOGIT_RTOL_WORST, "router": ROUTER_RTOL,
                        "near_tie": NEAR_TIE,
                        "min_long_compared": MIN_LONG_COMPARED,
                        "min_crossing_compared": min_crossing}}
    medians = [facts["logit_rel_err" + group]
               for group in ("", "_short", "_mid", "_long", "_crossing")]
    ok = (rows["finite"]
          and facts["long_compared"] >= MIN_LONG_COMPARED
          and facts["crossing_compared"] >= min_crossing
          and max(medians) <= LOGIT_RTOL
          and facts["logit_rel_err_high"] <= LOGIT_RTOL_HIGH
          and facts["logit_rel_err_worst"] <= LOGIT_RTOL_WORST
          and facts["router_rel_err"] <= ROUTER_RTOL)
    return bool(ok), facts


# ----------------------------------------------------------------------
# bytes and operations, for the hand rooflines (PERF.md section 5)
# ----------------------------------------------------------------------

def scan_flops(config, tokens):
    """Multiply-adds x 2 of ONE layer's chunked delta rule for a prefill
    of `tokens` positions (the bucket: the pad is computed), a chunk and a
    VALUE head — the kernel sees q and k repeated to the value heads —
    counted as `families/olmo_hybrid.py` `scan_flops` counts them."""
    _, h, dk, dv, _, _ = _linear(config)
    size = min(CHUNK, tokens)
    chunks = -(-tokens // size)
    a_chunk = (2 * 2 * size * size * dk + size * size * (dv + dk)
               + 3 * 2 * size * dk * dv + 2 * size * size * dv)
    return chunks * h * a_chunk


def scan_bytes(config, tokens):
    """What ONE layer's prefill op must move at the least, float32: the
    projection in, `y` out, and the window and state written once."""
    _, h, dk, dv, conv_dim, d_proj = _linear(config)
    taps = config["linear_conv_kernel_dim"]
    return 4 * (tokens * (d_proj + h * dv)
                + (taps - 1) * conv_dim + h * dk * dv)


def step_flops(config, rows):
    """ONE layer's decode step of `rows` rows, a state element: decay (1),
    the two products with the old state (2 each), the rank-one write (2);
    and the conv."""
    _, h, dk, dv, conv_dim, _ = _linear(config)
    return rows * (7 * h * dk * dv
                   + 2 * config["linear_conv_kernel_dim"] * conv_dim)


def step_bytes(config, rows):
    """ONE layer's: each row's state and window read once and written
    once, float32."""
    _, h, dk, dv, conv_dim, _ = _linear(config)
    return rows * 2 * 4 * (h * dk * dv
                           + (config["linear_conv_kernel_dim"] - 1) * conv_dim)


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


def ring_bytes(config, lengths, block=512):
    """The attention layers' K and V pages a step reads for rows at
    `lengths`: each as far as the kernel's block that holds `length`."""
    page = 2 * 4 * config["num_key_value_heads"] * config["head_dim"]
    layers = layer_kinds(config).count("attention")
    return layers * sum(page * (n // block + 1) * block for n in lengths)


def decode_bytes(config, rows, lengths, experts_hit):
    """Bytes ONE decode step of `rows` rows reads (and, for the state,
    writes), by part: every weight outside the routed experts once,
    `experts_hit` (a layer) of the held experts' matrices, the delta
    rule's state and window, the rings as far as they are filled."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = layer_kinds(config)
    shapes = param_shapes(config)
    count = lambda *tails: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if n.endswith(tails))
    return {"mixers": 4 * count("inproj_weight", "outproj_weight",
                                "conv_weight", "qkv_weight", "out_weight"),
            "shared_and_router": 4 * count(
                "router_weight", "shared_gate_weight", "shared_up_weight",
                "shared_down_weight", "shared_score_weight"),
            "experts": len(kinds) * expert_bytes(config, experts_hit),
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "state": kinds.count("linear_attention") * step_bytes(config, rows),
            "kv": ring_bytes(config, lengths)}
