"""Layers of ONE sublayer through `TransformerLM` and `GenerativeSession`:
Nemotron-H's stream (PR 64) — `hybrid_override_pattern` read as one layer a
character, `M` a Mamba-2 mixer of several groups whose gated norm goes by
group, `*` NoPE grouped-query attention, `E` UNGATED squared-ReLU experts
of two matrices beside a shared one under a sigmoid router with a selection
bias — whole and as ONE CHIP'S SHARE, against the plain reference of the
benchmark (benchmarks/reference/nemotron_h.py: float32 `jax.numpy` at
"highest", independent of `mxnet_tpu`).

Tiny widths (`MEM*EM`, hidden 32, 8 Mamba heads x 4 in 4 groups of 16
states, chunk 8, 4 query / 2 K/V heads of 8, 16 experts of width 24 of
which a share holds 4, 3 a token, a shared expert of 40; one test at a
width of 136, which the program STORES as 256), both sides float32 on the CPU: errors are float32
rounding (measured 2e-7 of the largest logit); the bound 1e-4 is far above
that and a fortieth of what one bfloat16 pass leaves.  The file costs about
50 s.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.families import nemotron_h as family  # noqa: E402
from benchmarks.reference import nemotron_h as reference  # noqa: E402

WHOLE = {"vocab_size": 40, "hidden_size": 32, "num_hidden_layers": 6,
         "hybrid_override_pattern": "MEM*EM",
         "mamba_num_heads": 8, "mamba_head_dim": 4, "ssm_state_size": 16,
         "n_groups": 4, "conv_kernel": 4, "chunk_size": 8,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "moe_intermediate_size": 24,
         "moe_shared_expert_intermediate_size": 40, "n_shared_experts": 1,
         "n_routed_experts": 16, "router_experts": 16, "held_experts": None,
         "num_experts_per_tok": 3, "norm_topk_prob": True,
         "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
         "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
         "max_position_embeddings": 64, "param_dtype": "float32",
         "state_dtype": "float32"}
HELD = 4
SHARES = [dict(WHOLE, n_routed_experts=HELD, held_experts=[first, HELD])
          for first in range(0, WHOLE["router_experts"], HELD)]
SHARE = SHARES[1]
RTOL = 1e-4  # of the largest |logit|; see the module docstring
EXPERT_KEYS = ("up_weight", "down_weight")
TOKENS = np.random.RandomState(64).randint(0, 40, 40)


def _share_of(params, config):
    """`params` of the whole layer cut to the experts `config` holds."""
    if config["held_experts"] is None:
        return params
    first, count = config["held_experts"]
    return {k: v[first:first + count]
            if k.split("_", 1)[1] in EXPERT_KEYS else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def params():
    import jax

    # the init's 0.02 makes every projection's output small against the
    # conv's bias and the gains; x5 makes every part of the stream matter
    # (the router is drawn by its logits' deviation, ~1 at any width)
    p = family.make_params(WHOLE, 5, jax.devices("cpu")[0])
    return {k: 5.0 * v if k.endswith("_weight") and "conv" not in k
            and "router" not in k else v for k, v in p.items()}


def _hold(params):
    return {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    return np.abs(got - want).max() / np.abs(want).max()


def _close(got, want, rtol=RTOL):
    assert _rel(got, want) <= rtol, _rel(got, want)


def _score(lm, held, tokens):
    t = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(held), {"data": (1, t)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(t, lm.vocab)


def _session(held, config, **kw):
    kw = dict(dict(max_sessions=3, max_len=48, max_decode_tokens=16,
                   seq_buckets=[8, 32]), **kw)
    return GenerativeSession("lm", family.model(config), held, **kw)


def _routed_ffn(config, params, u):
    """Layer 1's routed FFN node AS THE MODEL BUILDS IT (`_RoutedFFN.apply`
    -> `mx.sym.MoE` with the model's own attributes) on normed input `u
    (T, d)`: ``Routed(u) + Shared(u)`` of the experts `config` holds."""
    lm = family.model(config)
    ffn = lm._ffns[1]
    names = ffn.params(1)
    node = ffn.apply(mx.sym.Variable("u"), names, 1, None)
    held = {"l1_" + k: mx.nd.array(np.asarray(params["l1_" + k]))
            for k in names}
    pred = mx.Predictor(node, held, {"u": (1,) + u.shape})
    pred.forward(u=u[None])
    return pred.get_output(0)[0]


def _reference_ffn(config, params, u, shared=True):
    """The reference's layer 1 on `u`, which it norms itself: a gain of
    ones and rows of unit mean square make that the identity."""
    first = 0 if config["held_experts"] is None else config["held_experts"][0]
    p = {k: params["l1_" + k] for k in reference.ROUTED[1:]}
    return np.asarray(reference.expert_layer(
        u, np.ones(u.shape[1], np.float32), *p.values(),
        top_k=config["num_experts_per_tok"],
        scale=config["routed_scaling_factor"], first=first, eps=0.0,
        width=config["moe_intermediate_size"],
        shared_times=float(shared))[0])


def _normed_rows(t=24, seed=2):
    rng = np.random.RandomState(seed)
    u = rng.randn(t, WHOLE["hidden_size"]).astype(np.float32)
    return u / np.sqrt((u * u).mean(-1, keepdims=True))


# ----------------------------------------------------------------------
# (a) the model against the reference: scoring, training, the cache
# ----------------------------------------------------------------------

def test_a_published_layer_is_one_layer_of_one_sublayer():
    """The arguments are the configuration's; a layer has parameters, a
    norm, cache entries and counters for the half it HAS and nothing for
    the other."""
    args = family.model_args(SHARE)
    assert args["layer_types"] == ["mamba", "none", "mamba", "attention",
                                   "none", "mamba"]
    assert args["ffn_types"] == ["none", "routed", "none", "none", "routed",
                                 "none"]
    assert (args["expert_act"], args["expert_gated"]) == ("relu2", False)
    assert args["kind_specs"]["mamba"]["groups"] == 4
    assert args["expert_d_ff"] == family.stored_width(SHARE) == 24
    lm = family.model(SHARE)
    assert lm.mixed_symbol(2) is None and lm.extra_outputs() == ("moe_load",)
    assert list(lm.cache_spec(2)) == [
        "conv_state_0", "ssm_state_0", "conv_state_2", "ssm_state_2",
        "k_cache_3", "v_cache_3", "conv_state_5", "ssm_state_5"]
    names = set(lm.prefill_symbol().list_arguments())
    assert names == set(family.param_shapes(SHARE)) | {
        "data", "slot", "length", "last_token"} | set(lm.cache_spec(2))
    # ONE norm a layer: the mixer's `ln1` or the FFN's `ln2`
    assert {n for n in names if "_ln" in n and n != "ln_f_gamma"} == {
        "l0_ln1_gamma", "l1_ln2_gamma", "l2_ln1_gamma", "l3_ln1_gamma",
        "l4_ln2_gamma", "l5_ln1_gamma"}
    assert "l1_gate_weight" not in names and "l1_shared_gate_weight" not in names
    step = lm.call_counters(rows=2, lengths=(5, 9), computed=2, pages=3,
                            max_len=48, platform="cpu")
    assert step["moe.routed_pairs"] == 2 * 2 * 3      # two E layers
    page = 4 * (8 * 4 * 16 + 3 * (32 + 2 * 4 * 16))
    assert step["ssm.state_bytes"] == 3 * 2 * 2 * page   # three M layers
    fill = lm.call_counters(positions=32, platform="cpu")
    assert fill["ssm.scan_positions"] == 3 * 32
    assert fill["attn.prefill_positions"] == 32          # one * layer
    # two matrices an expert: what a step reads of a hit expert
    assert lm.step_weight_bytes()["mtp.step_bytes"] > 0
    assert lm._ffns[1].expert_keys == ("up_weight", "down_weight")


@pytest.mark.parametrize("which", ["whole", "share"])
@pytest.mark.parametrize("length", [2, 8, 21])
def test_score_symbol_matches_the_reference(params, which, length):
    config = WHOLE if which == "whole" else SHARE
    mine = _share_of(params, config)
    tokens = TOKENS[:length]
    _close(_score(family.model(config), _hold(mine), tokens),
           reference.logits(mine, config, tokens))


def test_the_training_graph_matches_the_references_loss_and_gradients(params):
    """`training_symbol` forward and backward against `jax.grad` of the
    reference's loss: the grouped gated norm, the groups' B and C, the
    squared ReLU of both kinds of expert, the router under its bias and
    every layer's ONE norm carry gradient."""
    import jax
    import jax.numpy as jnp

    lm = family.model(WHOLE)
    t = 24
    data, label = TOKENS[:t], TOKENS[1:t + 1]
    watch = ("l0_mnorm_gamma", "l0_conv_weight", "l0_inproj_weight",
             "l1_router_weight", "l1_up_weight", "l1_down_weight",
             "l1_shared_up_weight", "l1_ln2_gamma", "l2_A_log",
             "l3_qkv_weight", "l3_ln1_gamma", "l4_shared_down_weight",
             "l5_D", "embed_weight", "head_weight")

    def loss(p):
        return reference.loss(dict(params, **p), WHOLE, data.tolist(), label)

    want_loss, want = jax.value_and_grad(loss)(
        {k: jnp.asarray(params[k]) for k in watch})
    held = _hold(params)
    args = dict(held, data=mx.nd.array(data[None].astype(np.float32)),
                softmax_label=mx.nd.array(label[None].astype(np.float32)))
    grads = {k: mx.nd.zeros(v.shape) for k, v in held.items()}
    exe = lm.training_symbol().bind(mx.cpu(), args, args_grad=grads)
    exe.forward(is_train=True)
    prob = exe.outputs[0].asnumpy()
    got_loss = -np.log(prob[np.arange(t), label]).mean()
    np.testing.assert_allclose(got_loss, float(want_loss), rtol=1e-5)
    exe.backward()
    for name in watch:
        got, ref = grads[name].asnumpy(), np.asarray(want[name])
        assert np.abs(ref).max() > 0, name
        assert np.abs(got - ref).max() <= 1e-3 * np.abs(ref).max(), name


@pytest.mark.parametrize("which", ["whole", "share"])
def test_prefill_then_decode_through_the_cache_matches_one_full_forward(
        params, which):
    """Prefill (padded buckets: 11 in 32, 2 in 8 — fewer than the conv
    window holds) then ten decode steps of two sessions, one step of each
    in turn, through the session's own programs and state: every call's
    logits are the reference's at that position of that session's
    sequence — the whole layer, and one chip's share of it."""
    config = WHOLE if which == "whole" else SHARE
    mine = _share_of(params, config)
    seqs = [TOKENS[:21], TOKENS[21:33]]
    starts, slots, buckets = [11, 2], [2, 0], [32, 8]
    want = [np.asarray(reference.logits(mine, config, s)) for s in seqs]
    gs = _session(_hold(mine), config)
    try:
        for seq, n, slot, bucket, ref in zip(seqs, starts, slots, buckets,
                                             want):
            exe, fn = gs._program(gs._prefill_pred, 1, bucket, True)
            data = np.zeros((1, bucket), np.float32)
            data[0, :n] = seq[:n]
            got = gs._run(exe, fn, data, np.full((1,), slot, np.float32),
                          np.full((1,), n, np.float32))
            _close(got[0], ref[n - 1])
        exe, fn = gs._program(gs._decode_pred, 1, 1, False)
        for step in range(10):
            for seq, n, slot, ref in zip(seqs, starts, slots, want):
                t = n + step
                got = gs._run(exe, fn, np.asarray([[seq[t]]], np.float32),
                              np.full((1,), slot, np.float32),
                              np.full((1,), t, np.float32))
                _close(got[0], ref[t])
    finally:
        gs.close()


def _drive(gs, reqs):
    waiting = list(reqs)
    while waiting or gs.active():
        waiting = gs.admit(waiting)
        gs.decode_step()
    return [r.future.result(timeout=0) for r in reqs]


def test_the_batcher_serves_the_shares_greedy_tokens(params):
    """Five requests of mixed lengths and budgets through two slots of a
    share's session — the packed decode bucket, the run-ahead token feed,
    retirements and admissions (a prefill PROGRAM between steps) — give
    the reference's greedy tokens."""
    mine = _share_of(params, SHARE)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 40, n).tolist() for n in (9, 2, 17, 5, 12)]
    budgets = [6, 9, 3, 7, 5]

    def greedy(prompt, budget, width=32):
        toks = list(prompt)
        for _ in range(budget):
            padded = toks + [0] * (width - len(toks))
            toks.append(int(np.argmax(np.asarray(
                reference.logits(mine, SHARE, padded))[len(toks) - 1])))
        return toks[len(prompt):]

    gs = _session(_hold(mine), SHARE, max_sessions=2)
    try:
        results = _drive(gs, [GenerateRequest("lm", p, 60.0, b)
                              for p, b in zip(prompts, budgets)])
    finally:
        gs.close()
    for p, b, r in zip(prompts, budgets, results):
        assert r.tokens.tolist() == greedy(p, b), p


# ----------------------------------------------------------------------
# (b) the shares add up
# ----------------------------------------------------------------------

def test_the_four_shares_routed_terms_add_up_to_the_uncut_layer(params):
    """What the four chips of the tiny deployment compute for one layer —
    each its own four experts' terms for the tokens routed to them,
    through the model's own `mx.sym.MoE` node — with the shared expert,
    which every chip computes alike, counted once, is what the uncut
    reference gives for the whole layer; and the reference's own shares
    add up the same way."""
    u = _normed_rows()
    whole = _reference_ffn(WHOLE, params, u)
    shared = whole - _reference_ffn(WHOLE, params, u, shared=False)
    program = sum(_routed_ffn(c, _share_of(params, c), u) - shared
                  for c in SHARES) + shared
    _close(program, whole)
    plain = sum(_reference_ffn(c, _share_of(params, c), u, shared=False)
                for c in SHARES) + shared
    _close(plain, whole, rtol=1e-6)
    # no share is the whole, and none is empty
    for c in SHARES:
        part = _reference_ffn(c, _share_of(params, c), u, shared=False)
        assert 0.02 < np.abs(part).max() / np.abs(whole - shared).max() < 0.98


def test_a_width_of_no_whole_tiles_is_stored_padded_by_the_program():
    """An expert of 136 channels — over one 128-lane tile, no whole number
    of them — is STORED 256 wide by the layer itself
    (`transformer_lm.stored_width`; 24, within one tile, and 256 stay):
    `stored_params` pads a published stack with zeros and passes a stored
    one as the object it is; the layer on the padded stacks is the
    reference's on the published ones; the pad holds no gradient; a layer
    whose pad were NOT zero would leave the model; and a session handed
    the PUBLISHED shapes stores them itself."""
    from mxnet_tpu.models.transformer_lm import stored_width

    assert [stored_width(w) for w in (24, 128, 136, 256, 1856)] == [
        24, 128, 256, 256, 1920]
    wide = dict(SHARE, moe_intermediate_size=136)
    assert family.model_args(wide)["expert_d_ff"] == 136
    lm = family.model(wide)
    import jax

    mine = family.make_params(wide, 5, jax.devices("cpu")[0])
    mine = {k: 5.0 * np.asarray(v) if k.endswith("_weight")
            and "conv" not in k and "router" not in k else np.asarray(v)
            for k, v in mine.items()}
    up, down = (mine["l1_" + k] for k in EXPERT_KEYS)
    assert up.shape == (4, 32, 256) and down.shape == (4, 256, 32)
    assert not up[:, :, 136:].any() and not down[:, 136:].any()
    assert up[:, :, :136].all() and down[:, :136].all()
    assert lm.stored_params(mine)["l1_up_weight"] is up
    published = dict(mine, l1_up_weight=up[:, :, :136],
                     l1_down_weight=down[:, :136])
    again = lm.stored_params(published)
    assert np.array_equal(again["l1_up_weight"], up)
    assert np.array_equal(again["l1_down_weight"], down)
    with pytest.raises(ValueError, match="neither"):
        lm.stored_params(dict(mine, l1_up_weight=up[:, :, :100]))
    u = _normed_rows()
    want = _reference_ffn(wide, published, u)
    _close(_reference_ffn(wide, mine, u), want, rtol=1e-6)
    _close(_routed_ffn(wide, mine, u), want)
    dirty = dict(mine, l1_up_weight=np.where(up == 0, 0.1, up),
                 l1_down_weight=np.where(down == 0, 0.1, down))
    _close(_reference_ffn(wide, dirty, u), want, rtol=1e-6)
    assert _rel(_routed_ffn(wide, dirty, u), want) > 30 * RTOL
    # the pad holds no gradient: training keeps it zero
    ffn = lm._ffns[1]
    names = ffn.params(1)
    node = ffn.apply(mx.sym.Variable("u"), names, 1, None)
    args = {"l1_" + k: mx.nd.array(mine["l1_" + k]) for k in names}
    args["u"] = mx.nd.array(u[None])
    grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
    exe = mx.sym.sum(node * node).bind(mx.cpu(), args, args_grad=grads)
    exe.forward(is_train=True)
    exe.backward()
    got_up, got_down = (grads["l1_" + k].asnumpy() for k in EXPERT_KEYS)
    assert np.abs(got_up[:, :, :136]).max() > 0
    assert np.abs(got_down[:, :136]).max() > 0
    assert not got_up[:, :, 136:].any() and not got_down[:, 136:].any()
    # a session handed the published shapes binds the stored ones
    gs = _session(_hold(published), wide)
    try:
        exe, _ = gs._program(gs._decode_pred, 1, 1, False)
        assert exe.arg_dict["l1_up_weight"].shape == (4, 32, 256)
        assert np.array_equal(np.asarray(exe.arg_dict["l4_down_weight"]._data),
                              mine["l4_down_weight"])
    finally:
        gs.close()


# ----------------------------------------------------------------------
# (c) what a wrong program would compute, and the cell's check
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_fault_moves_the_logits_the_check_compares(params, fault):
    """Each of the reference's seeded faults — a gate matrix's product in
    the square's place, ``relu`` without the square, the gated norm over
    all channels or before the gate, heads mapped to groups by ``j mod G``,
    a rotary turn in the attention, the scale dropped, the selection bias
    in the weights, the weights not renormalised, softmax for sigmoid, the
    shared expert left out — moves the logits by far more than the bound,
    and the program agrees with the sound reference, not with it."""
    mine = _share_of(params, SHARE)
    tokens = TOKENS[:21]
    want = reference.logits(mine, SHARE, tokens)
    got = _score(family.model(SHARE), _hold(mine), tokens)
    _close(got, want)
    wrong = reference.forward(mine, SHARE, tokens, fault=fault)[0]
    assert _rel(wrong, want) > 30 * RTOL, fault
    assert _rel(got, wrong) > 30 * RTOL, fault


@pytest.fixture(scope="module")
def served(params):
    mine = {k: np.asarray(v) for k, v in _share_of(params, SHARE).items()}
    held = _hold(mine)
    gs = GenerativeSession("lm", family.model(SHARE), held, max_sessions=2,
                           max_len=48, max_decode_tokens=16,
                           seq_buckets=[8, 32])
    # the tenant's programs bind exactly what the check is handed
    bound = {k: v._data for k, v in held.items()}
    yield gs, bound
    gs.close()


@pytest.mark.parametrize("probe", [None, "bfloat16", "relu", "norm_one_group",
                                   "no_shared"])
def test_the_cells_check_passes_the_program_and_refuses_the_rest(
        served, probe, monkeypatch):
    """`check_against_reference` on a tiny tenant with every slot live: the
    program passes all four limits at float32 rounding; the bfloat16
    control — the reference with weights, activations and state in
    bfloat16 in the program's place — is refused by a logit limit TIGHTENED
    to what a CPU's float32 leaves room for (the chip's limits are read on
    the chip: `families/nemotron_h.py`), and a reference with a seeded
    fault refuses the sound program."""
    gs, bound = served
    if probe is not None:   # the CPU's float32: a hundredth of the chip's
        monkeypatch.setattr(family, "LOGIT_RTOL", 1e-4)
        monkeypatch.setattr(family, "LOGIT_RTOL_ROW", 2e-4)
    monkeypatch.setattr(family, "REFERENCE_PAD", 8)
    control = probe if probe == "bfloat16" else None
    fault = probe if probe in reference.FAULTS else None
    ok, facts = family.check_against_reference(
        SHARE, gs, bound, 11, 8, control=control, fault=fault)
    assert facts["rows_a_step"] == 2 and facts["not_as_stated"] == []
    assert facts["remaining_share"] >= family.MIN_COMPARED_SHARE
    if probe is None:
        assert ok, facts
        assert facts["logit_rel_err_high"] < 1e-5
        assert facts["prefill_state_rel_err"] < 1e-5
        assert facts["decode_state_rel_err"] < 1e-5
    else:
        assert not ok and facts["logit_rel_err"] > 1e-3, facts


# ----------------------------------------------------------------------
# (d) what the spec refuses
# ----------------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(layer_types=["mamba", "none"], ffn_types=["none", "none"]),
     "neither"),
    (dict(layer_types=["none", "attention"], ffn_types=["routed", "none"],
          router_input="mixer"), "router_input"),
    (dict(layer_types=["attention"] * 2, ffn_types=["dense"] * 2,
          num_experts=0, expert_gated=False), "expert_gated"),
    (dict(expert_act="gelu"), "expert_act"),
    (dict(kind_specs={"mamba": dict(heads=8, head_dim=4, state=16,
                                    groups=3)},
          layer_types=["mamba", "none"]), "mamba_groups")])
def test_a_spec_that_cannot_be_built_is_refused(change, match):
    base = dict(vocab=40, num_layers=2, num_heads=4, d_model=32, norm="rms",
                positions="none", bias=False,
                layer_types=["attention", "none"],
                ffn_types=["none", "routed"], num_experts=4,
                experts_per_token=2, expert_d_ff=128)
    TransformerLM(**base)
    with pytest.raises(ValueError, match=match):
        TransformerLM(**dict(base, **change))


def test_a_draft_module_takes_the_last_layers_one_sublayer():
    """`nextn` under a pattern whose last layer has no mixer: the module's
    block is that layer's FFN alone, with no cache entry of its own."""
    lm = TransformerLM(vocab=40, num_layers=2, num_heads=4, d_model=32,
                       norm="rms", positions="none", bias=False,
                       layer_types=["attention", "none"],
                       ffn_types=["none", "dense"], nextn=1)
    assert list(lm.cache_spec(2)) == ["k_cache_0", "v_cache_0"]
    args = lm.decode_symbol().list_arguments()
    assert "l2_ffn1_weight" in args and "l2_ln1_gamma" not in args
