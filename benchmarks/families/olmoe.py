"""The `olmoe` family: how a configuration file becomes the model under
test (`models.TransformerLM` with OLMoE's block: RMSNorm, rotary
positions, QK-norm, a dropless routed SwiGLU layer, no biases, untied
head), its seeded weights and its comparison with the plain reference."""
import numpy as np

from ..reference import olmoe as reference

INIT_STD = 0.02  # OLMoE's `initializer_range`
# Router columns are drawn wider.  At 0.02 the router's softmax over 64
# is nearly flat, the 8 chosen experts carry 8/64 of its mass and a bit,
# and — the scores being used unnormalised — the whole expert layer is a
# tenth of the attention's part of the residual stream: a dropped expert
# or a renormalised gate would hide under the rounding of one bfloat16
# pass.  At 0.05 router logits have a standard deviation of about 2.3 and
# the 8 chosen carry about four fifths of the mass, as a trained router's
# do.  WHICH experts a token picks stays uniform, so the load, the
# experts hit and the bytes read are the same.
ROUTER_STD = 0.05
CHECK_PROMPTS = 8
CHECK_PROMPT_LEN = 24
CHECK_STEPS = 8
# The model under test holds f32 weights and multiplies at JAX's default
# precision, which on a TPU is one bfloat16 pass; the reference multiplies
# at "highest" (the router does in both).  Logits then differ by 0.5-0.9%
# of the row's largest (measured on the v5e, PR 25: PERF.md section 6).
# Router probabilities differ too, by about 1e-4 (a hidden state off by
# half a percent moves a logit of spread 2.3 by ~5e-3, and p is ~0.03 at
# the eighth rank), and the eighth and ninth of 64 lie closer than that
# in a good share of (row, layer) pairs: where they do, the two sides may
# keep different experts, and that row's logits differ by one expert's
# whole term, 1-2.5% measured — another rounding of the same model, not a
# fault.  So rows whose margin is under NEAR_TIE in any layer are counted
# and skipped (measured: 3 of 8 such rows carried a swap's error, 4 of 28
# of the others — a swap at an EARLIER position reaches a row through
# attention, which no rule on the row's own margin removes).
NEAR_TIE = 2e-4
# The compared rows are therefore held to two bounds, each as a share of
# the row's largest |reference logit|.  Their MEDIAN, which the few rows
# with a swap upstream cannot move: 0.7% measured, bound 1.2%; the eighth
# expert dropped for every token reads 2.3%, a renormalised gate 8.7%,
# decode's rotary position off by one 11%, theta 5e5 33% (measured on the
# v5e, PR 25), and fp8 or int8 weights round 8 to 30 times coarser than
# the bfloat16 pass that makes the 0.7%.  Their WORST, for a fault in one
# slot or one step: 1.6-3.7% measured over six seeds (a swap upstream),
# bound 6%.
LOGIT_RTOL = 1.2e-2
LOGIT_RTOL_WORST = 6e-2
MIN_COMPARED_SHARE = 0.5


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(vocab=config["vocab_size"],
                         num_layers=config["num_hidden_layers"],
                         num_heads=config["num_attention_heads"],
                         d_model=config["hidden_size"],
                         d_ff=config["intermediate_size"],
                         max_len=config["max_position_embeddings"],
                         norm="rms", norm_eps=config["rms_norm_eps"],
                         positions="rotary",
                         rope_theta=config["rope_theta"], qk_norm=True,
                         num_experts=config["num_experts"],
                         experts_per_token=config["num_experts_per_tok"],
                         bias=False, tied_head=False)


def param_shapes(config):
    d, ff = config["hidden_size"], config["intermediate_size"]
    e, v = config["num_experts"], config["vocab_size"]
    shapes = {"embed_weight": (v, d), "head_weight": (v, d),
              "ln_f_gamma": (d,)}
    per_layer = {"ln1_gamma": (d,), "qkv_weight": (3 * d, d),
                 "qnorm_gamma": (d,), "knorm_gamma": (d,),
                 "out_weight": (d, d), "ln2_gamma": (d,),
                 "router_weight": (d, e), "gate_weight": (e, d, ff),
                 "up_weight": (e, d, ff), "down_weight": (e, ff, d)}
    for i in range(config["num_hidden_layers"]):
        for n, s in per_layer.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, from the seed, in the dtype they are
    served in: matrices and embeddings N(0, INIT_STD), the router
    N(0, ROUTER_STD), norm gains 1 + N(0, 0.1) so that a gain that is
    dropped or crossed shows.  One
    jitted call a tensor (one program a shape): a single call for 7.5 GB
    would hold every tensor's intermediates at once."""
    import functools

    import jax
    import jax.numpy as jnp

    # a program whose TransformerLM lacks this block's arguments fails
    # here, at once, not after 7.5 GB of weights are made
    model(config)

    @functools.partial(jax.jit, static_argnames=("shape",))
    def normal(key, mean, std, shape):
        return mean + std * jax.random.normal(
            key, shape, jnp.dtype(config["param_dtype"]))

    key = jax.random.key(seed)
    out = {}
    with jax.default_device(device):
        for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
            gain = name.endswith("_gamma")
            std = (0.1 if gain else ROUTER_STD
                   if name.endswith("_router_weight") else INIT_STD)
            out[name] = normal(jax.random.fold_in(key, i),
                               1.0 if gain else 0.0, std, shape)
    return out


def check_against_reference(config, session, params, seed, bucket):
    """Prefill (in the warm prefill program of length `bucket`) then
    CHECK_STEPS greedy decode steps through the tenant's own programs
    and KV ring, every step's logits against ONE full forward of the
    plain reference over the final sequence (causal: row t is the answer
    after t+1 tokens).  Rows where the reference's router has a near tie
    in any layer (NEAR_TIE) are counted and skipped; at least
    MIN_COMPARED_SHARE of the rows must remain, their median error
    within LOGIT_RTOL and their worst within LOGIT_RTOL_WORST.  The
    benchmark's one reach into the session's private
    `_program` / `_run` (as `chip_smoke.py` does).  The caller guarantees
    the batcher is idle and slot 0 free.  Returns (ok, facts)."""
    rng = np.random.default_rng(seed)
    n = min(CHECK_PROMPT_LEN, bucket - 1)
    errs, margins, finite = [], [], True
    for _ in range(CHECK_PROMPTS):
        toks = [int(t) for t in rng.integers(0, config["vocab_size"], n)]
        exe, fn = session._program(session._prefill_pred, 1, bucket, True)
        data = np.zeros((1, bucket), np.float32)
        data[0, :n] = toks
        got = [session._run(exe, fn, data, np.zeros((1,), np.float32),
                            np.full((1,), n, np.float32))[0]]
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        for _ in range(CHECK_STEPS):
            toks.append(int(np.argmax(got[-1])))
            got.append(session._run(
                exe, fn, np.asarray([[toks[-1]]], np.float32),
                np.zeros((1,), np.float32),
                np.full((1,), len(toks) - 1, np.float32))[0])
        ref, margin = reference.forward(params, config, toks)
        ref = np.asarray(ref, np.float64)
        margin = np.asarray(margin).min(axis=0)  # over the layers
        for i, row in enumerate(got):
            want = ref[n - 1 + i]
            finite = finite and bool(np.isfinite(row).all())
            errs.append(float(np.abs(row - want).max() / np.abs(want).max()))
            margins.append(float(margin[n - 1 + i]))
    errs, margins = np.asarray(errs), np.asarray(margins)
    clear = margins >= NEAR_TIE
    compared = np.sort(errs[clear]) if clear.any() else np.asarray([np.inf])
    facts = {"logit_rel_err": float(np.median(compared)),
             "logit_rel_err_worst": float(compared[-1]),
             "compared": int(clear.sum()), "skipped": int((~clear).sum()),
             "logit_rel_err_skipped": float(errs[~clear].max())
             if (~clear).any() else 0.0,
             "prompts": CHECK_PROMPTS, "steps": CHECK_STEPS}
    ok = (finite and clear.mean() >= MIN_COMPARED_SHARE
          and facts["logit_rel_err"] <= LOGIT_RTOL
          and facts["logit_rel_err_worst"] <= LOGIT_RTOL_WORST)
    return bool(ok), facts
