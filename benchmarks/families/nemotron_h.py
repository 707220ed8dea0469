"""The `nemotron_h` family: how a configuration file becomes the model under
test (`models.TransformerLM` with Nemotron-H's stream: `hybrid_override_pattern`
read as ONE `TransformerLM` layer a character, each of ONE sublayer — `M` a
Mamba-2 mixer of `n_groups` groups whose gated norm goes by group, `*` NoPE
grouped-query attention, `E` `num_experts_per_tok` of `router_experts`
UNGATED squared-ReLU experts of two matrices beside a shared one, sigmoid
scores with a selection bias, renormalised and scaled — held as ONE CHIP'S
SHARE of the deployment the file states), its seeded weights, its comparison
with the plain reference, and the operations and bytes of its kernels.  What
`families/granite_hybrid.py` draws and checks of a Mamba-2 block, and
`families/granite_moe_hybrid.py` of a routed share under one, is imported
from there."""
import numpy as np

from ..reference import nemotron_h as reference
from . import granite_hybrid as _granite
from .afmoe import BIAS_STD, _no_chip_favoured, router_error as _router_error
from .granite_hybrid import (A_RANGE, CONV_BOUND, DT_RANGE, INIT_STD,  # noqa: F401
                             LONG_STEPS, _not_as_stated)
from .granite_moe_hybrid import MID_SHARE, check_plans  # noqa: F401
from .qwen3_next import _serve_rows

# Router columns N(0, ROUTER_LOGIT_STD / sqrt(hidden)) — N(0, 0.0193) at the
# published hidden size of 2,688: the normed stream has unit RMS, so router
# logits have a standard deviation of about 1 and the six chosen sigmoid
# scores of 128 run from ~0.85 to ~0.93 — visibly different, so a weight
# that is not renormalised (the six would sum to ~5.3, not 1), not scaled by
# 2.5, taken after the bias or from a softmax shows.  The selection bias is
# `afmoe.BIAS_STD`'s N(0, 0.05), of the order of the gap between
# neighbouring scores at the sixth rank.  WHICH experts a token picks stays
# near uniform, so load, experts hit and bytes read are those of a balanced
# trained router.
ROUTER_LOGIT_STD = 1.0

# THE CHECK, through the timed tenant's own programs and state, with EVERY
# SLOT LIVE (`granite_moe_hybrid.check_plans`: `granite_hybrid.check_prompts`'
# four sequences — three quarters of the largest bucket, 2 tokens (fewer
# than the conv window holds), two thirds of the smallest bucket, 0.6 of the
# second largest — then MID_SHARE of the tenant's buckets in turn; at the
# cell's ladder 6,016 in 8,192, 2 in 4,096, 2,752 in 4,096, 4,368 in 7,168,
# 2,560 in 4,096, 3,200 in 5,120, 3,840 in 6,144, 4,480 in 7,168): each
# prompt prefilled ALONE into a slot drawn from the seed through the
# prefill program of its bucket, then LONG_STEPS (256) greedy decode steps
# of ALL rows at once through the 8-row decode program, LOGITS against the
# reference's full forward pass over each row's final sequence.
#
# Rows where the reference's router has a near tie THAT THIS CHIP FEELS in
# any routed layer are counted and skipped (`families/mistral4.py`'s rule
# and reason: the program's projections multiply at one bfloat16 pass, its
# normed stream differs from the reference's by parts in a thousand, and
# where two candidates' biased scores lie closer than that the two sides
# keep different experts — another rounding of the same model).  The margin
# is `reference.route`'s, the least over the routed layers, as a share of
# the last kept SCORE (a sigmoid's ~0.85: a margin of 0.002 is a logit gap
# of ~0.013, what one bfloat16 pass moves a logit by).  It clears the rows
# whose OWN choice is on an edge and no more: a swap at an earlier position
# has moved the Mamba-2 states and the K/V every later position reads, so
# the error's tail stays (0.9 quantile 13.1% with no row skipped, 7.5% at
# 0.002, 5.3% at 0.01 where 18% of the rows are left: seed 2147483701) and
# only the MEDIANS are judged.
NEAR_TIE = 0.002
# FOUR LIMITS in granite's form (`families/granite_moe_hybrid.py` says why
# no single one sees everything the configuration states).  Readings: my
# chip runs, PR 64, TPU v5e; PERF.md section 6 lists them.  SOUND: the
# cell's tenant.  CONTROL: the reference with weights, activations and
# state in bfloat16 in the program's place ON THE SAME SEQUENCES the
# program had generated (`control="bfloat16"`), which must fail.
#
# 1. STORED AS STATED, exact (`granite_hybrid._not_as_stated`).
# 2. PREFILL_STATE_RTOL, the scan at G = 8: layer 0's conv window and
#    state on each row's slot after its prefill against the reference's
#    with the SAME one-pass input projection (`reference.first_mixer_state`).
# 3. DECODE_STATE_RTOL, the step kernel at G = 8: the same after the last
#    decode step, 256 steps behind the prefill.
# 4. LOGIT_RTOL / LOGIT_RTOL_ROW, the whole model: each compared position's
#    largest logit difference as a share of the row's largest |reference
#    logit| — the MEDIAN over all compared positions (what is wrong in every
#    row: a gain, the gates' form, a lower precision) and the LARGEST OF THE
#    ROWS' OWN MEDIANS (one row of the step, one bucket's program).  The 0.9
#    quantile and the worst position are reported, not judged: upstream
#    swaps make them.
#
# SOUND (the cell's tenant on seventeen seeds, 2147483701, -711 to -714,
# -721 to -727, -751 to -753, -762, 3000000761, four of them on both of this PR's draws of the
# experts) / what must fail / the limit (PERF.md section 6,
# PR 64, has every reading).  Limits 2 and 3 have their upper reading from
# THIS tenant with its recurrent state STORED IN BFLOAT16 (every `state`
# entry of the session rounded to bfloat16 after every program call, seed
# 2147483753: the reference's bfloat16 control stands in for the logits
# alone and leaves the session's state as it is, so it cannot feel them);
# limit 4 from the bfloat16 control (seeds 2147483701 and -727):
#   prefill state  the worst row 3.0e-5 to 1.71e-4 (the 2-token prompt
#                  8e-8) / the bfloat16 state 1.65e-3 to 1.70e-3, every row
#                  (one rounding: what the dtype leaves of any array) /
#                  5e-4: 2.9 times the sound largest, a third of the stored
#   decode state   the worst row 5.5e-6 to 1.75e-4 (it scatters: a row's
#                  state after 256 steps) / the bfloat16 state 3.8e-3 to
#                  5.7e-3 a row, the judged worst row 5.7e-3 / 2e-3: eleven
#                  times the sound largest, 2.8 times under the stored's
#                  worst row and 1.9 under its least (3e-3 until the stored
#                  state was read: nearer to it than to the sound)
#   median         1.16-1.31% / 2.97%, 3.31% / 2.0%: the geometric middle
#                  of 1.31 and 2.97 (the control 2.3 times the sound
#                  largest — one bfloat16 pass of every projection is IN
#                  the sound reading already, so bfloat16 everywhere adds
#                  one more rounding of the same size, not an order: the
#                  limit has 1.5 times of room on either side, over a
#                  sound spread of 1.13 times on seventeen seeds)
#   a row's own    the largest 1.35-2.24% (the rows' own 1.09-2.24%) / 4.55%,
#                  4.51% (its rows' own 3.09-4.55%) / 3.2%: the geometric
#                  middle of 2.24 and 4.55 (2.0 times)
# (reported beside them: 0.9 quantile 5.7-9.0% against the control's 14.0%,
# the worst position 28-58%; compared 71-75% of 2,056 positions.  The
# bfloat16 STATE reads 1.28% / 1.34% under limit 4, beside the sound 1.19% /
# 1.69% of its seed: the logits do not feel it, limits 2 and 3 do.)
PREFILL_STATE_RTOL = 5e-4
DECODE_STATE_RTOL = 2e-3
LOGIT_RTOL = 2.0e-2
LOGIT_RTOL_ROW = 3.2e-2
HIGH_QUANTILE = 0.9
# of all rows, how many have to clear NEAR_TIE for the median to mean
# anything
MIN_COMPARED_SHARE = 0.4
# the program's own router function against the float32 product at
# "highest": float32 at "highest" reads ~1e-6, one bfloat16 pass 2e-3
ROUTER_RTOL = 1e-4
# the reference's sequences are ALL padded to the longest one's multiple of
# this, so that one set of compiled programs serves every row
REFERENCE_PAD = 1024

_MIXER = {"M": "mamba", "*": "attention", "E": "none"}
_FFN = {"M": "none", "*": "none", "E": "routed"}


def held_experts(config):
    """(first, count) of the routed experts this chip holds, or None for
    the whole layer."""
    if config.get("held_experts") is None:
        assert config["n_routed_experts"] == config["router_experts"]
        return None
    first, count = config["held_experts"]
    assert count == config["n_routed_experts"]
    return int(first), int(count)


def model_args(config):
    """`TransformerLM`'s arguments for this configuration: a published layer
    is ONE layer, of one sublayer."""
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == config["num_hidden_layers"], pattern
    assert not set(pattern) - set(_MIXER), pattern    # (no dense `-` layer)
    return dict(
        vocab=config["vocab_size"], num_layers=len(pattern),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_model=config["hidden_size"],
        max_len=config["max_position_embeddings"], norm="rms",
        norm_eps=config["layer_norm_epsilon"], positions="none",
        bias=False, tied_head=config["tie_word_embeddings"],
        layer_types=[_MIXER[c] for c in pattern],
        ffn_types=[_FFN[c] for c in pattern],
        kind_specs={"mamba": dict(
            heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            state=config["ssm_state_size"], groups=config["n_groups"],
            conv=config["conv_kernel"], chunk=config["chunk_size"])},
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        shared_d_ff=(config["n_shared_experts"]
                     * config["moe_shared_expert_intermediate_size"]),
        router_score="sigmoid", router_bias=True,
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
        expert_act=config["mlp_hidden_act"], expert_gated=False,
        held_experts=held_experts(config))


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(**model_args(config))


def param_shapes(config):
    """Every parameter's shape AS PUBLISHED (the program may store a
    stack wider: `TransformerLM.stored_params`)."""
    d, v = config["hidden_size"], config["vocab_size"]
    f = config["moe_intermediate_size"]
    sf = config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"]
    total, held = config["router_experts"], config["n_routed_experts"]
    heads, taps = config["mamba_num_heads"], config["conv_kernel"]
    d_inner = heads * config["mamba_head_dim"]
    conv_dim = d_inner + 2 * config["n_groups"] * config["ssm_state_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    kinds = {
        "M": {"ln1_gamma": (d,),
              "inproj_weight": (d_inner + conv_dim + heads, d),
              "conv_weight": (taps, conv_dim), "conv_bias": (conv_dim,),
              "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
              "mnorm_gamma": (d_inner,), "outproj_weight": (d, d_inner)},
        "*": {"ln1_gamma": (d,), "qkv_weight": (q + 2 * kv, d),
              "out_weight": (d, q)},
        "E": {"ln2_gamma": (d,), "router_weight": (d, total),
              "router_bias": (total,), "up_weight": (held, d, f),
              "down_weight": (held, f, d), "shared_up_weight": (d, sf),
              "shared_down_weight": (sf, d)}}
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        for n, s in kinds[kind].items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are served
    in, drawn as `families/granite_hybrid.py` draws a Mamba-2 model's (its
    constants; that file says why each) and `families/afmoe.py` a sigmoid
    router's: matrices, the embedding and the head N(0, INIT_STD); norm
    gains and `D` 1 + N(0, 0.1); `A_log` = log U(A_RANGE), `dt_bias` the
    inverse softplus of a log-uniform DT_RANGE; the conv's taps and bias
    U(+-CONV_BOUND); the router N(0, ROUTER_LOGIT_STD / sqrt(hidden)) and
    its selection bias N(0, BIAS_STD), both so that the draw favours no
    chip (`afmoe._no_chip_favoured`).  One jitted call a tensor, drawn AS
    PUBLISHED and handed to the program's own loader a tensor at a time
    (`TransformerLM.stored_params`: the device never holds a second copy of
    the weights; the reference reads the published columns of what comes
    back)."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this stream's arguments fails
    # here, at once, not after 8.6 GB of weights are made
    stored = model(config).stored_params
    dtype = jnp.dtype(config["param_dtype"])
    router_std = ROUTER_LOGIT_STD / config["hidden_size"] ** 0.5

    @functools.partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        if kind in ("matrix", "router", "bias"):
            std = {"matrix": INIT_STD, "router": router_std,
                   "bias": BIAS_STD}[kind]
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
             "router_weight": "router", "router_bias": "bias"}
    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            tail = name.split("_", 1)[1]
            kind = kinds.get(tail, kinds.get(tail.rsplit("_", 1)[-1], "matrix"))
            out[name] = draw(jax.random.fold_in(key, i), kind, shape)
            if kind in ("router", "bias"):
                out[name] = _no_chip_favoured(out[name],
                                              config["n_routed_experts"])
            out[name] = stored({name: out[name]})[name]
    return out


def first_routed(config):
    return config["hybrid_override_pattern"].index("E")


def router_error(params, config):
    """`afmoe.router_error` on the first routed layer's router."""
    return _router_error(params, dict(config,
                                      num_dense_layers=first_routed(config)))


def _first_mixer_err(config, session, params, toks, slot, width):
    """Limits 2 and 3: layer 0's window and state at `slot` against the
    reference's after `toks` (padded to `width`), each difference's norm as
    a share of the reference's own; the larger."""
    names = list(session._spec)
    errs = []
    padded = list(toks) + [0] * (width - len(toks))
    for name, want in zip(("conv_state_0", "ssm_state_0"),
                          reference.first_mixer_state(params, config, padded,
                                                      len(toks))):
        got = np.asarray(session._state[names.index(name)][slot], np.float64)
        want = np.asarray(want, np.float64)
        errs.append(float(np.linalg.norm(got - want)
                          / max(np.linalg.norm(want), 1e-30)))
    return max(errs)


def check_rows(config, session, params, seed, bucket, control=None,
               fault=None):
    """The rows of the check the module's head describes, served and
    compared: `err` (each compared position's largest logit difference as a
    share of the row's largest |reference logit|), `margin` (the reference
    router's over the held experts, the least over the routed layers) and
    `row` over all rows' compared positions, layer 0's state errors `filled`
    / `stepped` a row, and `finite`, `prompts`, `buckets`, `steps`.
    `control`: a dtype in which the REFERENCE, on the sequences the program
    generated, stands in for the program's logits; `fault`: one of
    `reference.FAULTS` that the reference then HAS (the tests' probe of what
    the check feels)."""
    import time

    started = time.perf_counter()

    def said(what):     # (a check of minutes says where they go)
        print("[bench] check: %s at %.1f s" % (
            what, time.perf_counter() - started), flush=True)

    rng = np.random.default_rng(seed)
    plans, steps = check_plans(session, bucket)
    prompts = [[int(t) for t in rng.integers(0, config["vocab_size"], n)]
               for n, _ in plans]
    slots = rng.permutation(session._slots)[:len(plans)]
    first_is_mamba = config["hybrid_override_pattern"][0] == "M"
    buckets, vocab = [t for _, t in plans], config["vocab_size"]

    # ONE padded length for every sequence the reference sees (causal: no
    # compared row sees the pad; `first_mixer_state` stops at the true
    # length): its programs then compile once — a forward pass is under a
    # second, its programs a minute a length (my chip runs, PR 64)
    longest = max(n for n, _ in plans) + steps
    width = -(-longest // REFERENCE_PAD) * REFERENCE_PAD

    def state_errs(seqs):
        return [_first_mixer_err(config, session, params, toks, slot, width)
                for toks, slot in zip(seqs, slots)] if first_is_mamba else []

    # every prompt prefilled alone into its slot, and layer 0's state read
    # there; then the same once more (a prefill writes its slot whole) with
    # the steps behind it
    _serve_rows(session, prompts, buckets, slots, 0, vocab)
    filled = state_errs(prompts)
    got, seqs = _serve_rows(session, prompts, buckets, slots, steps, vocab)
    said("%d rows served, %d steps" % (len(plans), steps))
    stepped = state_errs(seqs)
    said("layer 0's states compared")
    out = {"err": [], "margin": [], "row": []}
    for r, ((n, _), toks, mine) in enumerate(zip(plans, seqs, got)):
        rows = list(range(n - 1, n + steps))
        padded = toks + [0] * (width - len(toks))
        ref, margin = reference.forward(params, config, padded, rows=rows,
                                        fault=fault)
        ref = np.asarray(ref, np.float64)
        if control is not None:
            mine = np.asarray(reference.forward(
                params, config, padded, rows=rows, dtype=control)[0],
                np.float32)
        out["err"].extend(np.abs(mine - ref).max(axis=-1)
                          / np.abs(ref).max(axis=-1))
        out["margin"].extend(np.asarray(margin).min(axis=0)[rows])  # layers
        out["row"].extend([r] * len(rows))
        said("row %d of %d positions compared" % (r, len(padded)))
    out = {k: np.asarray(v) for k, v in out.items()}
    return dict(out, finite=bool(np.isfinite(got).all()), filled=filled,
                stepped=stepped, steps=steps,
                prompts=[n for n, _ in plans], buckets=buckets)


def check_against_reference(config, session, params, seed, bucket,
                            control=None, fault=None):
    """`check_rows` judged by the four limits above, and the router's
    precision.  The caller guarantees the batcher is idle and every slot
    free.  Returns (ok, facts)."""
    rows = check_rows(config, session, params, seed, bucket, control, fault)
    errs = rows["err"]
    clear = rows["margin"] >= NEAR_TIE

    def stat(mask, reduce):
        return float(reduce(errs[mask])) if mask.any() else float("inf")

    not_as_stated = _not_as_stated(config, session, params)
    by_row = [stat(clear & (rows["row"] == r), np.median)
              for r in range(len(rows["prompts"]))]
    facts = {"logit_rel_err": stat(clear, np.median),
             "logit_rel_err_high": stat(
                 clear, lambda e: np.quantile(e, HIGH_QUANTILE)),
             "logit_rel_err_worst": stat(clear, np.max),
             "logit_rel_err_skipped": stat(~clear, np.max),
             "logit_rel_err_all": float(np.median(errs)),
             "by_row": by_row, "logit_rel_err_row": max(by_row),
             "prefill_state_rel_err": max(rows["filled"], default=0.0),
             "prefill_state": rows["filled"],
             "decode_state_rel_err": max(rows["stepped"], default=0.0),
             "decode_state": rows["stepped"],
             "not_as_stated": not_as_stated[:8],
             "router_rel_err": router_error(params, config),
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "remaining_share": float(clear.mean()),
             "skipped_because": "in some routed layer a held expert's "
             "biased score lies closer than near_tie (of the last kept "
             "score) to the edge of the reference's choice",
             "rows_a_step": len(rows["prompts"]), "steps": rows["steps"],
             "prompts": rows["prompts"], "buckets": rows["buckets"],
             "control": control, "fault": fault,
             "limits": {"median": LOGIT_RTOL, "row_median": LOGIT_RTOL_ROW,
                        "prefill_state": PREFILL_STATE_RTOL,
                        "decode_state": DECODE_STATE_RTOL,
                        "router": ROUTER_RTOL, "near_tie": NEAR_TIE,
                        "min_compared_share": MIN_COMPARED_SHARE}}
    ok = (rows["finite"] and not not_as_stated
          and facts["remaining_share"] >= MIN_COMPARED_SHARE
          and facts["logit_rel_err"] <= LOGIT_RTOL
          and facts["logit_rel_err_row"] <= LOGIT_RTOL_ROW
          and facts["prefill_state_rel_err"] <= PREFILL_STATE_RTOL
          and facts["decode_state_rel_err"] <= DECODE_STATE_RTOL
          and facts["router_rel_err"] <= ROUTER_RTOL)
    return bool(ok), facts


# ----------------------------------------------------------------------
# operations and bytes, for the hand rooflines (PERF.md sections 5-6): ONE
# layer's, float32
# ----------------------------------------------------------------------

def _granite_keys(config):
    """The Mamba-2 sizes under the names `families/granite_hybrid.py`'s
    four functions read them by."""
    return {"mamba_n_heads": config["mamba_num_heads"],
            "mamba_d_head": config["mamba_head_dim"],
            "mamba_d_state": config["ssm_state_size"],
            "mamba_n_groups": config["n_groups"],
            "mamba_d_conv": config["conv_kernel"],
            "mamba_chunk_size": config["chunk_size"]}


def scan_flops(config, tokens):
    """`granite_hybrid.scan_flops` (ONE Mamba layer's chunked scan over a
    bucket of `tokens`, counted once; `highest` makes each product six
    bfloat16 passes) at this configuration's eight groups."""
    return _granite.scan_flops(_granite_keys(config), tokens)


def scan_bytes(config, tokens):
    return _granite.scan_bytes(_granite_keys(config), tokens)


def step_flops(config, rows):
    return _granite.step_flops(_granite_keys(config), rows)


def step_bytes(config, rows):
    """Each row's state and window read once and written once."""
    return _granite.step_bytes(_granite_keys(config), rows)


def ungated_matmul_flops(config, pair_rows):
    """The two segment matmuls of an ungated expert layer over `pair_rows`
    gathered rows: up ``[rows, d] x [d, f]`` and down ``[rows, f] x [f,
    d]``, multiply-adds x 2 (a gated layer's would be three), at the
    PUBLISHED width (the pad's zero columns do no model's work)."""
    return 2 * 2 * pair_rows * (config["hidden_size"]
                                * config["moe_intermediate_size"])


def stored_width(config):
    """An expert's width as the PROGRAM stores it (its own rule: whole
    lane tiles, the pad zero) — what a call reads and writes."""
    from mxnet_tpu.models.transformer_lm import stored_width as rule

    return rule(config["moe_intermediate_size"])


def ungated_matmul_bytes(config, pair_rows, experts_hit):
    """What the two calls must move at the least: `experts_hit` experts'
    two matrices once AS STORED, the rows in (d), the hidden rows out and
    in again (f), the results out (d)."""
    d, f = config["hidden_size"], stored_width(config)
    return 4 * (experts_hit * 2 * d * f + pair_rows * 2 * (d + f))


def expected_experts_hit(config, rows):
    """Held experts hit by a step of `rows` rows under uniform routing."""
    held, total = config["n_routed_experts"], config["router_experts"]
    return held * (1.0 - (1.0 - config["num_experts_per_tok"] / total) ** rows)


def decode_bytes(config, rows, lengths, experts_hit, block=512):
    """Bytes ONE decode step of `rows` rows reads (and, for the state,
    writes), by part: every weight outside the routed experts once,
    `experts_hit` (a layer) of the held experts' two matrices, the Mamba
    layers' state and window, the attention layers' rings as far as the
    kernel's blocks of `block` are filled, the untied head."""
    d, v = config["hidden_size"], config["vocab_size"]
    pattern = config["hybrid_override_pattern"]
    shapes = param_shapes(config)
    count = lambda *tails: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if n.endswith(tails))
    page = 2 * 4 * config["num_key_value_heads"] * config["head_dim"]
    return {"mamba": 4 * count("inproj_weight", "outproj_weight",
                               "conv_weight"),
            "attention": 4 * count("qkv_weight", "out_weight"),
            "shared_and_router": 4 * count(
                "router_weight", "shared_up_weight", "shared_down_weight"),
            "experts": pattern.count("E") * 4 * experts_hit * 2 * (
                d * stored_width(config)),
            "head": 4 * v * d, "embedding": 4 * rows * d,
            "state": pattern.count("M") * step_bytes(config, rows),
            "kv": pattern.count("*") * sum(
                page * (n // block + 1) * block for n in lengths)}


def prefill_flops(config, tokens):
    """Matmul operations of ONE prefill of a bucket of `tokens` positions,
    by part (multiply-adds x 2, counted once — the scan's run six passes):
    the dense projections, the held share of the routed pairs under uniform
    routing, the shared experts, causal attention, the scan."""
    d = config["hidden_size"]
    pattern = config["hybrid_override_pattern"]
    shapes = param_shapes(config)
    count = lambda *tails: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if n.endswith(tails))
    pairs = (tokens * config["num_experts_per_tok"]
             * config["n_routed_experts"] / config["router_experts"])
    dh, heads = config["head_dim"], config["num_attention_heads"]
    return {"mamba_proj": 2 * tokens * count("inproj_weight",
                                             "outproj_weight"),
            "attention_proj": 2 * tokens * count("qkv_weight", "out_weight"),
            "shared_and_router": 2 * tokens * count(
                "router_weight", "shared_up_weight", "shared_down_weight"),
            "experts": pattern.count("E") * ungated_matmul_flops(config,
                                                                 pairs),
            "attention": pattern.count("*") * 2 * 2 * heads * dh * (
                tokens * tokens // 2),
            "scan": pattern.count("M") * scan_flops(config, tokens),
            "head": 2 * d * config["vocab_size"]}
