"""The `opt` family: how a configuration file becomes the model under
test (`models.TransformerLM`, block for block OPT), its seeded weights
and its comparison with the plain reference."""
import numpy as np

from ..reference import opt as reference

INIT_STD = 0.02  # OPT's `init_std`
CHECK_PROMPTS = 2
CHECK_PROMPT_LEN = 24
CHECK_STEPS = 8
# The model under test holds f32 weights and multiplies at JAX's default
# precision, which on a TPU is one bfloat16 pass (relative rounding
# 2^-9 per product, f32 accumulation); the reference multiplies at
# "highest".  Over 24 layers that comes to under a percent of the largest
# logit (measured, PR 22: see PERF.md section 6).  The bound is about
# three times that: fp8 or int8 weights, or a wrong cache position,
# miss it by far.
LOGIT_RTOL = 2e-2


def model(config):
    from mxnet_tpu.models import TransformerLM

    return TransformerLM(vocab=config["vocab_size"],
                         num_layers=config["num_hidden_layers"],
                         num_heads=config["num_attention_heads"],
                         d_model=config["hidden_size"],
                         d_ff=config["ffn_dim"],
                         max_len=config["max_position_embeddings"])


def param_shapes(config):
    d, ff = config["hidden_size"], config["ffn_dim"]
    shapes = {"embed_weight": (config["vocab_size"], d),
              "pos_weight": (config["max_position_embeddings"], d),
              "ln_f_gamma": (d,), "ln_f_beta": (d,)}
    per_layer = {"ln1_gamma": (d,), "ln1_beta": (d,),
                 "qkv_weight": (3 * d, d), "qkv_bias": (3 * d,),
                 "out_weight": (d, d), "out_bias": (d,),
                 "ln2_gamma": (d,), "ln2_beta": (d,),
                 "ffn1_weight": (ff, d), "ffn1_bias": (ff,),
                 "ffn2_weight": (d, ff), "ffn2_bias": (d,)}
    for i in range(config["num_hidden_layers"]):
        for n, s in per_layer.items():
            shapes["l%d_%s" % (i, n)] = s
    return shapes


def make_params(config, seed, device):
    """All weights on `device`, in one jitted call from the seed, in the
    dtype they are served in: weights N(0, INIT_STD), LayerNorm gains 1,
    LayerNorm shifts 0, projection biases N(0, INIT_STD) so that a bias
    that is dropped shows."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    dtype = jnp.dtype(config["param_dtype"])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith("_beta"):
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, dtype)
        return out

    with jax.default_device(device):
        return jax.jit(make)(jax.random.key(seed))


def check_against_reference(config, session, params, seed, bucket):
    """Prefill (in the warm prefill program of length `bucket`) then
    CHECK_STEPS greedy decode steps through the tenant's own programs
    and KV ring, every step's logits against ONE full forward of the
    plain reference over the final sequence (causal: row t is the answer
    after t+1 tokens).  Logits, not tokens: the served API returns
    tokens, so this is the benchmark's one reach into the session's
    private `_program` / `_run` (as `chip_smoke.py` does).  The caller
    guarantees the batcher is idle and slot 0 free.  Returns (ok, facts)."""
    rng = np.random.default_rng(seed)
    n = min(CHECK_PROMPT_LEN, bucket - 1)
    worst, finite = 0.0, True
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
        ref = np.asarray(reference.logits(params, config, toks), np.float64)
        for i, row in enumerate(got):
            want = ref[n - 1 + i]
            finite = finite and bool(np.isfinite(row).all())
            worst = max(worst, float(np.abs(row - want).max()
                                     / np.abs(want).max()))
    return bool(finite and worst <= LOGIT_RTOL), {
        "logit_rel_err": worst, "prompts": CHECK_PROMPTS,
        "steps": CHECK_STEPS}
