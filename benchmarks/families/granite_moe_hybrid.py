"""The `granite_moe_hybrid` family: how a configuration file becomes the
model under test (`models.TransformerLM` with Granite 4.0-H Small's block:
a layer pattern of Mamba-2 and grouped-query NoPE attention mixers and in
EVERY layer ten of 72 routed SwiGLU experts of width 768 — the ten largest
router logits, a softmax over them — beside one shared MLP of width 1,536
that every token passes, RMSNorm, the four multipliers, tied head — held
as ONE CHIP'S SHARE of the deployment the file states), its seeded
weights, its comparison with the plain reference, and the bytes and
operations of its programs.  What `families/granite_hybrid.py` already
draws and checks of the Mamba-2 block is imported from there."""
import numpy as np

from ..reference import granite_moe_hybrid as reference
from .afmoe import _no_chip_favoured, router_error as _router_error
from .qwen3_next import _serve_rows
from .granite_hybrid import (A_RANGE, CONV_BOUND, DT_RANGE, INIT_STD,  # noqa: F401
                             LONG_STEPS, _not_as_stated, check_prompts,
                             scan_bytes, scan_flops, step_bytes, step_flops)

# Router columns N(0, ROUTER_LOGIT_STD / sqrt(hidden)) — N(0, 0.02) at the
# published hidden size of 4,096: the normed stream has unit RMS, so router
# logits have a standard deviation of about 1.3 at any width; the ten
# largest of 72 then run over ~1.5 in logit and the softmax over them from
# ~0.04 to ~0.25 — visibly different, so a softmax over all 72 that is not
# renormalised (the ten would sum to ~0.5), uniform weights or ten of the
# HELD experts alone show.  WHICH experts a token picks stays near
# uniform, so load, experts hit and bytes read are those of a balanced
# trained router.
ROUTER_LOGIT_STD = 1.28

# THE CHECK, through the timed tenant's own programs and state, with EVERY
# SLOT LIVE: one prompt a slot, each prefilled alone through the prefill
# program of its bucket (as the batcher prefills), then `steps` greedy
# decode steps of ALL rows at once through the decode program of as many
# rows as the tenant has slots — at 8 slots the 8-row step that is ~90% of
# the cell's window.  The rows, in slots drawn from the seed (a row's
# place in the step is not its slot):
#
# (a) `granite_hybrid.check_prompts`' sequences (as many as there are
#     slots): about three quarters of the largest bucket, 2 tokens (fewer
#     than the conv window holds), two thirds of the smallest bucket, and
#     about 0.6 of the second largest — at the cell's ladder (128, 256,
#     512, 1,024) and chunk 256: 752 in 1,024 (two chunks and 240
#     positions, 272 of pad), 2 in 128, 86 in 128, 312 in 512 — none a
#     multiple of the chunk;
# (b) in every other slot a prompt of MID_SHARE of a bucket through the
#     tenant's buckets in turn (80 in 128, 160 in 256, 320 in 512, 640 in
#     1,024), so that every prefill program hands its state to the step.
#
# Every row steps `granite_hybrid.LONG_STEPS` times (256; fewer where the
# longest would pass the ring): what rounds a little at every step has 256
# steps to show in.
MID_SHARE = 5 / 8
# Rows where the reference's router has a near tie THAT THIS CHIP FEELS in
# any of the layers are counted and skipped, as the other shares' are and
# for their reason (`families/mistral4.py`): the model under test
# multiplies its projections at one bfloat16 pass, its normed stream
# differs from the reference's by a few parts in a thousand and a router
# logit by as much (the router itself is float32 at "highest" on both
# sides), and where two candidates lie closer than that the two sides keep
# different experts: another rounding of the same model, not a fault.  The
# margin is `reference.route`'s: the least distance of a HELD expert's
# probability (of the softmax over all 72) from the edge of the choice, as
# a share of the tenth probability — for two neighbours a logit gap g
# reads 1 - exp(-g).  Here a swap costs little — ten gates of ~0.1 under a
# branch scaled by 0.22: with NO row skipped the 0.99 quantile reads 0.78%
# against 0.59% at 0.01, and the margin hardly moves the median (0.450% at
# 0, 0.435 at 0.01, 0.431 at 0.02, 0.441 at 0.05: seed 3100502) — so the
# threshold is low, and two thirds of the rows are compared (0.05 left a
# seventh).
NEAR_TIE = 0.01
# FOUR LIMITS, granite's (`families/granite_hybrid.py` says why no single
# one sees everything the configuration states), the fourth in the share
# families' form.  Readings: my chip runs, PR 54, TPU v5e; PERF.md section
# 6 lists them.  SOUND: the cell's tenant, below.  CONTROLS (seed
# 3100502): the reference with weights, activations and state in
# bfloat16 in the program's place ON THE SAME SEQUENCES the program had
# generated (`control="bfloat16"`, through this same comparison); every
# recurrent buffer of the session rounded to bfloat16 after every call; the
# scan's block products at one bfloat16 pass (`ops.ssm._HIGHEST` None).
#
# 1. STORED AS STATED, exact (`granite_hybrid._not_as_stated`): every
#    parameter the tenant's programs bind is `param_dtype` and holds the
#    values the tenant was handed, every buffer of its `cache_spec` is
#    `state_dtype`.
#
# 2. PREFILL_STATE_RTOL, the scan: layer 0's conv window and state on
#    each row's slot after its prefill against the reference's with the
#    SAME one-pass input projection (`reference.first_mixer_state`), each
#    difference's norm as a share of the reference's; the worst row.
#
# 3. DECODE_STATE_RTOL, the one-step update: the same after the last
#    decode step, 256 steps behind the prefill.  (The 8-row step's
#    projections are on the MXU at the stated one pass, as the
#    reference's here: this reads a hundredth of what
#    granite-4.0-h-micro's check reads through its ONE-row program, whose
#    projections XLA compiles more exactly than stated.)
#
# 4. LOGIT_RTOL / LOGIT_RTOL_HIGH, the whole model: over the compared rows
#    (those whose margin clears NEAR_TIE), each row's largest logit
#    difference as a share of the row's largest |reference logit| against
#    ONE full float32 forward of the reference at "highest" over that
#    row's final sequence: the MEDIAN (what is wrong in every row: a gain,
#    a multiplier, the gates' form, a lower precision) and the
#    HIGH_QUANTILE (a fault in a tenth of the rows or more: one row of the
#    step, one bucket's program).  The worst compared and the worst
#    skipped row are reported, not judged: one swapped expert upstream
#    makes them.
#
# SOUND (eleven runs of the cell on eleven seeds, six of them at this
# NEAR_TIE) / the controls, which must fail / the limit:
#   prefill state  every row 6e-8 to 1.82e-4 (the 2-token prompt 1e-7) /
#                  bfloat16 state 1.64e-3 to 1.67e-3 on every row, the
#                  scan at one pass 2.15e-3 to 2.41e-3 / 5e-4: the
#                  geometric middle of 1.82e-4 and 1.64e-3, and what
#                  granite-4.0-h-micro's check has
#   decode state   every row 2e-7 to 1.90e-4 / bfloat16 state 5.9e-2 to
#                  9.2e-2 (the scan at one pass reads 4e-6 to 1.8e-4: the
#                  step has no block product) / 3e-3: the geometric middle
#   median         0.432-0.535% (a row's own: 0.37-0.60%) / the bfloat16
#                  reference 1.71%, the bfloat16 state 1.02% (read at a
#                  margin of 0.05, as its 0.9 quantile) / 0.95%:
#                  the geometric middle of 0.535 and 1.71
#   0.9 quantile   0.512-0.637% / the bfloat16 reference 2.70%, the
#                  bfloat16 state 2.70% / 1.3%: the geometric middle
# (the scan at one pass leaves the logits where they were, 0.437 / 0.514%:
# only limit 2 sees it).  Compared 66-84% of 2,056 rows; the worst
# compared row 0.81-1.52%, the worst skipped 1.00-1.66%.
PREFILL_STATE_RTOL = 5e-4
DECODE_STATE_RTOL = 3e-3
LOGIT_RTOL = 9.5e-3
LOGIT_RTOL_HIGH = 1.3e-2
HIGH_QUANTILE = 0.9
# of all rows, how many have to clear NEAR_TIE for the median to mean
# anything
MIN_COMPARED_SHARE = 0.4
# the program's own router function against the float32 product at
# "highest" (`families/afmoe.py` `router_error` says why it is checked
# where it is stated): float32 at "highest" reads ~1e-6, one bfloat16
# pass 2e-3
ROUTER_RTOL = 1e-4
# the reference's sequences are padded to a multiple of this, so that one
# compiled program serves every row (causal: no compared row sees the pad)
REFERENCE_PAD = 256


def held_experts(config):
    """(first, count) of the routed experts this chip holds, or None for
    the whole layer."""
    if config.get("held_experts") is None:
        assert config["num_local_experts"] == config["router_experts"]
        return None
    first, count = config["held_experts"]
    assert count == config["num_local_experts"]
    return int(first), int(count)


def model_args(config):
    """`TransformerLM`'s arguments for this configuration."""
    layers = config["num_hidden_layers"]
    return dict(
        vocab=config["vocab_size"], num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        max_len=config["max_position_embeddings"], norm="rms",
        norm_eps=config["rms_norm_eps"], positions="none", bias=False,
        tied_head=config["tie_word_embeddings"], ffn="swiglu",
        layer_types=config["layer_types"], ffn_types=["routed"] * layers,
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_state=config["mamba_d_state"],
        mamba_groups=config["mamba_n_groups"],
        mamba_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["intermediate_size"],
        shared_d_ff=config["shared_intermediate_size"],
        # softmax over all 72, the ten largest, renormalised over the ten:
        # the published softmax over the ten kept logits
        router_score="softmax", route_norm=True,
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def param_shapes(config):
    d = config["hidden_size"]
    xf, sf = config["intermediate_size"], config["shared_intermediate_size"]
    total, held = config["router_experts"], config["num_local_experts"]
    heads, taps = config["mamba_n_heads"], config["mamba_d_conv"]
    d_inner = heads * config["mamba_d_head"]
    conv_dim = d_inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    dh = d // config["num_attention_heads"]
    qkv = d + 2 * config["num_key_value_heads"] * dh
    shapes = {"embed_weight": (config["vocab_size"], d), "ln_f_gamma": (d,)}
    routed = {"ln1_gamma": (d,), "ln2_gamma": (d,),
              "router_weight": (d, total),
              "gate_weight": (held, d, xf), "up_weight": (held, d, xf),
              "down_weight": (held, xf, d), "shared_gate_weight": (d, sf),
              "shared_up_weight": (d, sf), "shared_down_weight": (sf, d)}
    mixers = {
        "mamba": {"inproj_weight": (d_inner + conv_dim + heads, d),
                  "conv_weight": (taps, conv_dim), "conv_bias": (conv_dim,),
                  "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
                  "mnorm_gamma": (d_inner,), "outproj_weight": (d, d_inner)},
        "attention": {"qkv_weight": (qkv, d), "out_weight": (d, d)}}
    for i, kind in enumerate(config["layer_types"]):
        for n, s in {**routed, **mixers[kind]}.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in, drawn as `families/granite_hybrid.py` draws a Mamba-2
    model's (its constants; that file says why each) and
    `families/afmoe.py` a routed one's: matrices and the embedding N(0,
    INIT_STD); norm gains and `D` 1 + N(0, 0.1); `A_log` = log U(A_RANGE),
    `dt_bias` the inverse softplus of a log-uniform DT_RANGE; the conv's
    taps and bias U(+-CONV_BOUND); the router N(0, ROUTER_LOGIT_STD /
    sqrt(hidden)) with each chip's columns summing to zero, so that the
    draw favours no chip (`afmoe._no_chip_favoured`).  One jitted call a
    tensor."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 8.2 GB of weights are made
    model(config)
    dtype = jnp.dtype(config["param_dtype"])
    router_std = ROUTER_LOGIT_STD / config["hidden_size"] ** 0.5

    @functools.partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        if kind in ("matrix", "router"):
            std = router_std if kind == "router" else INIT_STD
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

    kinds = {"gamma": "gain", "D": "gain", "conv_weight": "conv",
             "conv_bias": "conv", "A_log": "A_log", "dt_bias": "dt_bias",
             "router_weight": "router"}
    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            tail = name.split("_", 1)[1]
            kind = kinds.get(tail, kinds.get(tail.rsplit("_", 1)[-1], "matrix"))
            out[name] = draw(jax.random.fold_in(key, i), kind, shape)
            if kind == "router":
                out[name] = _no_chip_favoured(out[name],
                                              config["num_local_experts"])
    return out


def router_error(params, config):
    """`afmoe.router_error` on layer 0's router."""
    return _router_error(params, dict(config, num_dense_layers=0))


def check_plans(session, bucket):
    """(prompt length, prefill bucket) of each slot's row, and the decode
    steps every row takes: `check_prompts`' sequences, then MID_SHARE of
    the tenant's buckets in turn."""
    ladder, slots = session._seq_ladder, session._slots
    assert bucket == min(ladder), (bucket, ladder)
    plans = [(n, t) for n, t, _ in check_prompts(ladder, session._max_len)]
    plans += [(max(1, int(ladder[i % len(ladder)] * MID_SHARE)),
               ladder[i % len(ladder)]) for i in range(slots - len(plans))]
    plans = plans[:slots]
    return plans, min(LONG_STEPS, session._max_len - max(n for n, _ in plans))


def _first_mixer_err(config, session, params, toks, slot):
    """Limits 2 and 3: layer 0's window and state at `slot` against the
    reference's after `toks`, each difference's norm as a share of the
    reference's own; the larger."""
    names = list(session._spec)
    errs = []
    for name, want in zip(("conv_state_0", "ssm_state_0"),
                          reference.first_mixer_state(params, config, toks)):
        got = np.asarray(session._state[names.index(name)][slot], np.float64)
        want = np.asarray(want, np.float64)
        errs.append(float(np.linalg.norm(got - want)
                          / max(np.linalg.norm(want), 1e-30)))
    return max(errs)


def check_rows(config, session, params, seed, bucket, control=None):
    """The rows of the check the module's head describes, served and
    compared: `err` (each compared position's largest logit difference as
    a share of the row's largest |reference logit|), `margin` (the
    reference router's over the held experts, the least over the layers)
    and `row` over all rows' compared positions, layer 0's state errors
    `filled` / `stepped` a row, and `finite`, `prompts`, `buckets`,
    `steps`.  `control`: a dtype in which the REFERENCE, on the sequences
    the program generated, stands in for the program's logits."""
    rng = np.random.default_rng(seed)
    plans, steps = check_plans(session, bucket)
    prompts = [[int(t) for t in rng.integers(0, config["vocab_size"], n)]
               for n, _ in plans]
    slots = rng.permutation(session._slots)[:len(plans)]
    first_is_mamba = config["layer_types"][0] == "mamba"
    buckets, vocab = [t for _, t in plans], config["vocab_size"]

    def state_errs(seqs):
        return [_first_mixer_err(config, session, params, toks, slot)
                for toks, slot in zip(seqs, slots)] if first_is_mamba else []

    # every prompt prefilled alone into its slot, and layer 0's state read
    # there; then the same once more (a prefill writes its slot whole) with
    # the steps behind it
    _serve_rows(session, prompts, buckets, slots, 0, vocab)
    filled = state_errs(prompts)
    got, seqs = _serve_rows(session, prompts, buckets, slots, steps, vocab)
    stepped = state_errs(seqs)
    out = {"err": [], "margin": [], "row": []}
    for r, ((n, _), toks, mine) in enumerate(zip(plans, seqs, got)):
        rows = list(range(n - 1, n + steps))
        padded = toks + [0] * (-len(toks) % REFERENCE_PAD)
        ref, margin = reference.forward(params, config, padded, rows=rows)
        ref = np.asarray(ref, np.float64)
        if control is not None:
            mine = np.asarray(reference.forward(
                params, config, padded, rows=rows, dtype=control)[0],
                np.float32)
        out["err"].extend(np.abs(mine - ref).max(axis=-1)
                          / np.abs(ref).max(axis=-1))
        out["margin"].extend(np.asarray(margin).min(axis=0)[rows])  # layers
        out["row"].extend([r] * len(rows))
    out = {k: np.asarray(v) for k, v in out.items()}
    return dict(out, finite=bool(np.isfinite(got).all()), filled=filled,
                stepped=stepped, steps=steps,
                prompts=[n for n, _ in plans], buckets=buckets)


def check_against_reference(config, session, params, seed, bucket,
                            control=None):
    """`check_rows` judged by the four limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, bucket, control)
    errs = rows["err"]
    clear = rows["margin"] >= NEAR_TIE

    def stat(mask, reduce):
        return float(reduce(errs[mask])) if mask.any() else float("inf")

    not_as_stated = _not_as_stated(config, session, params)
    facts = {"logit_rel_err": stat(clear, np.median),
             "logit_rel_err_high": stat(
                 clear, lambda e: np.quantile(e, HIGH_QUANTILE)),
             "logit_rel_err_worst": stat(clear, np.max),
             "logit_rel_err_skipped": stat(~clear, np.max),
             "by_row": [stat(clear & (rows["row"] == r), np.median)
                        for r in range(len(rows["prompts"]))],
             "prefill_state_rel_err": max(rows["filled"], default=0.0),
             "prefill_state": rows["filled"],
             "decode_state_rel_err": max(rows["stepped"], default=0.0),
             "decode_state": rows["stepped"],
             "not_as_stated": not_as_stated[:8],
             "router_rel_err": router_error(params, config),
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some layer a held expert's router "
             "probability lies closer than near_tie (of the last kept one) "
             "to the edge of the reference's choice",
             "rows_a_step": len(rows["prompts"]), "steps": rows["steps"],
             "prompts": rows["prompts"], "buckets": rows["buckets"],
             "control": control,
             "limits": {"median": LOGIT_RTOL,
                        "q%d" % round(100 * HIGH_QUANTILE): LOGIT_RTOL_HIGH,
                        "prefill_state": PREFILL_STATE_RTOL,
                        "decode_state": DECODE_STATE_RTOL,
                        "router": ROUTER_RTOL, "near_tie": NEAR_TIE,
                        "min_compared_share": MIN_COMPARED_SHARE}}
    ok = (rows["finite"] and not not_as_stated
          and facts["remaining_share"] >= MIN_COMPARED_SHARE
          and facts["logit_rel_err"] <= LOGIT_RTOL
          and facts["logit_rel_err_high"] <= LOGIT_RTOL_HIGH
          and facts["prefill_state_rel_err"] <= PREFILL_STATE_RTOL
          and facts["decode_state_rel_err"] <= DECODE_STATE_RTOL
          and facts["router_rel_err"] <= ROUTER_RTOL)
    return bool(ok), facts


# ----------------------------------------------------------------------
# bytes and operations, for the hand rooflines (PERF.md section 5); the
# two state-space programs' are `granite_hybrid`'s (`scan_flops`,
# `scan_bytes`, `step_flops`, `step_bytes`: ONE Mamba layer), imported
# ----------------------------------------------------------------------

def expert_bytes(config, experts_hit):
    """ONE layer's routed experts' matrices a step reads: `experts_hit`
    of the held ones, three matrices of ``d x f`` each, float32."""
    return 4 * experts_hit * 3 * (config["hidden_size"]
                                  * config["intermediate_size"])


def expected_experts_hit(config, rows):
    """Held experts hit by a step of `rows` rows under uniform routing."""
    held, total = config["num_local_experts"], config["router_experts"]
    k = config["num_experts_per_tok"]
    return held * (1.0 - (1.0 - k / total) ** rows)


def decode_bytes(config, rows, lengths, experts_hit, block=512):
    """Bytes ONE decode step of `rows` rows reads (and, for the state,
    writes), by part: every weight outside the routed experts once,
    `experts_hit` (a layer) of the held experts' matrices, the Mamba
    layers' state and window, the attention layers' rings as far as the
    kernel's blocks of `block` are filled."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"]
    shapes = param_shapes(config)
    count = lambda *tails: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if n.endswith(tails))
    page = 2 * 4 * config["num_key_value_heads"] * (
        d // config["num_attention_heads"])
    return {"mamba": 4 * count("inproj_weight", "outproj_weight",
                               "conv_weight"),
            "attention": 4 * count("qkv_weight", "out_weight"),
            "shared_and_router": 4 * count(
                "router_weight", "shared_gate_weight", "shared_up_weight",
                "shared_down_weight"),
            "experts": len(kinds) * expert_bytes(config, experts_hit),
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "state": kinds.count("mamba") * step_bytes(config, rows),
            "kv": kinds.count("attention") * sum(
                page * (n // block + 1) * block for n in lengths)}
