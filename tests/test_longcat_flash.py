"""LongCat-Flash's block through `TransformerLM` and `GenerativeSession`:
a published layer as TWO layers — two latent-attention sublayers whose
value (8) is narrower than their key (8 + 4) and whose latents are
rescaled, two dense SwiGLUs, and a routed layer forked from the first
FFN's normed input and joined at the second FFN's end (``ffn_types``
``("shortcut", "dense")``) — with zero-compute experts behind the real
ones in ONE softmax router (``zero_experts``), a selection bias, weights 6
p not renormalised, held as one chip's share of experts AND heads —
against the plain reference of the benchmark
(benchmarks/reference/longcat_flash.py: float32 `jax.numpy` at "highest",
no cache, independent of `mxnet_tpu`).

Tiny widths that keep the SHAPE of the model (2 published layers = 4
layers, hidden 48, 4 heads of 8 + 4 over a value of 8, ranks 24 and 16, 8
real experts and 8 zero-compute ones, 3 a token), both sides float32 on
the CPU: errors are float32 rounding; the bound 1e-4 is far above that and
a fortieth of what one bfloat16 pass leaves.  The file costs about 60 s.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.families import longcat_flash as family  # noqa: E402
from benchmarks.families.mistral4 import layout_rows  # noqa: E402
from benchmarks.reference import longcat_flash as reference  # noqa: E402

ASSUMED = {k: {"value": False}
           for k in ("norm_topk_prob", "router_bias", "tie_word_embeddings")}
CONFIG = {"vocab_size": 67, "hidden_size": 48, "ffn_hidden_size": 64,
          "expert_ffn_hidden_size": 16, "num_layers": 2,
          "num_attention_heads": 4, "held_heads": [0, 4],
          "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 4,
          "qk_nope_head_dim": 8, "v_head_dim": 8, "mla_scale_q_lora": True,
          "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
          "n_routed_experts": 8, "router_experts": 16, "zero_expert_num": 8,
          "zero_expert_type": "identity", "moe_topk": 3,
          "held_experts": [0, 8], "max_position_embeddings": 96,
          "rms_norm_eps": 1e-5, "rope_theta": 10000000,
          "attention_method": "MLA", "attention_bias": False,
          "assumed": ASSUMED, "param_dtype": "float32"}
RTOL = 1e-4  # of the largest |logit|; see the module docstring
FELT = 3e-3  # a fault moves a logit by at least this share of the largest
# the faults a `TransformerLM` argument spells: the program so built is the
# reference's control of that name
PROGRAM_FAULTS = {"renormalised": dict(route_norm=True),
                  "no_route_scale": dict(route_scale=1.0)}
TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 67, 48)]


def _draw(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x10 makes every part of the block matter (the router's
    # columns stay, and its bias grows to where it moves a choice of 3 of
    # 16)
    p = family.make_params(config, seed, jax.devices("cpu")[0])
    return {k: np.asarray(
        v if k.endswith(("_gamma", "_router_weight")) else 10.0 * v)
        for k, v in p.items()}


@pytest.fixture(scope="module")
def params():
    return _draw(CONFIG)


@pytest.fixture(scope="module")
def published(params):
    return family.checkpoint_layout(params, CONFIG)


def _hold(params):
    return {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}


@pytest.fixture(scope="module")
def held(params):
    return _hold(params)


def _far(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return np.abs(got - want).max() / np.abs(want).max()


def _close(got, want, rtol=RTOL):
    assert _far(got, want) <= rtol, _far(got, want)


def _model(config=CONFIG, **change):
    return TransformerLM(**dict(family.model_args(config), **change))


def _score(lm, held, tokens):
    t = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(held), {"data": (1, t)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(t, lm.vocab)


def _session(held, lm=None, **kw):
    kw = dict(dict(max_sessions=3, max_len=64, max_decode_tokens=40,
                   seq_buckets=[16, 32]), **kw)
    return GenerativeSession("lm", lm or family.model(CONFIG), held, **kw)


def _prefill(session, toks, bucket, slot=0):
    exe, fn = session._program(session._prefill_pred, 1, bucket, True)
    data = np.zeros((1, bucket), np.float32)
    data[0, :len(toks)] = toks
    return session._run(exe, fn, data, np.full((1,), slot, np.float32),
                        np.full((1,), len(toks), np.float32))[0]


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------

def test_the_family_builds_two_layers_a_published_one():
    args = family.model_args(CONFIG)
    assert args["ffn_types"] == ["shortcut", "dense"] * 2
    assert args["layer_types"] == ["latent_attention"] * 4
    assert (args["num_experts"], args["zero_experts"]) == (8, 8)
    assert args["route_norm"] is False and args["route_scale"] == 6.0
    lm = family.model(CONFIG)
    assert lm.mixed_symbol(3) is None          # a latent kind has none
    # two latent rings a published layer, 16 + 4 floats a position
    spec = lm.cache_spec(3, 64)
    assert list(spec) == ["latent_cache_%d" % i for i in range(4)]
    assert {e.shape for e in spec.values()} == {(3, 1, 20, 64)}
    assert lm.extra_outputs() == ("moe_load",)
    shapes = family.param_shapes(CONFIG)
    assert set(shapes) == set(lm.score_symbol().list_arguments()) - {"data"}
    # the router is 8 + 8 wide, the bias with it; a dense layer has none
    assert shapes["l0_router_weight"] == (48, 16)
    assert shapes["l2_router_bias"] == (16,)
    assert "l1_router_weight" not in shapes


def test_score_symbol_matches_the_reference(params, published, held):
    _close(_score(family.model(CONFIG), held, TOKENS),
           reference.logits(published, CONFIG, TOKENS))


def test_the_training_graph_matches_the_references_loss_and_gradients(
        published, held):
    """`training_symbol` forward and backward against `jax.grad` of the
    reference's loss: the carried branch, the identity term and the router
    under it, the rescaled latents and both sublayers' norms all carry
    gradient."""
    import jax
    import jax.numpy as jnp

    lm = family.model(CONFIG)
    t = 24
    data, label = np.asarray(TOKENS[:t]), np.asarray(TOKENS[1:t + 1])
    watch = ("l0_router_weight", "l0_gate_weight", "l0_ln2_gamma",
             "l1_ffn2_weight", "l1_kvb_weight", "l2_kva_norm_gamma",
             "l3_ln2_gamma", "embed_weight")

    def loss(p):
        logp = jax.nn.log_softmax(
            reference.logits(dict(published, **p), CONFIG, data.tolist()),
            axis=-1)
        return -jnp.take_along_axis(logp, jnp.asarray(label)[:, None],
                                    axis=-1).mean()

    want_loss, want = jax.value_and_grad(loss)(
        {k: jnp.asarray(published[k]) for k in watch})
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


@pytest.mark.parametrize("prompt,bucket", [(5, 16), (16, 16), (23, 32)])
def test_prefill_then_decode_through_the_rings_matches_the_reference(
        prompt, bucket, published, held):
    """Prefill (the up-projected form, the value carried at the key's
    width) and then every decode step to position 48 (the absorbed form
    over two rings a published layer) against ONE full forward."""
    session = _session(held)
    try:
        got = [_prefill(session, TOKENS[:prompt], bucket)]
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        zero = np.zeros((1,), np.float32)
        for t in range(prompt, len(TOKENS)):
            got.append(session._run(
                exe, fn, np.asarray([[TOKENS[t]]], np.float32), zero,
                np.full((1,), t, np.float32))[0])
    finally:
        session.close()
    want = np.asarray(reference.logits(published, CONFIG, TOKENS))
    _close(np.asarray(got), want[prompt - 1:])


def test_the_batcher_with_every_slot_live_emits_the_references_tokens(
        published, held):
    session = _session(held)
    plans = ((0, 20), (3, 9), (7, 30))
    try:
        reqs = [GenerateRequest("lm", TOKENS[a:a + n], 30.0, 10)
                for a, n in plans]
        assert session.admit(reqs) == []
        while session.active():
            session.decode_step()
    finally:
        session.close()
    for (a, n), r in zip(plans, reqs):
        tokens = list(r.future.result(timeout=5).tokens)
        assert len(tokens) == 10
        want = np.asarray(reference.logits(published, CONFIG,
                                           TOKENS[a:a + n] + tokens))
        assert tokens == [int(t) for t in want[n - 1:-1].argmax(axis=-1)]


# ----------------------------------------------------------------------
# the shares add up
# ----------------------------------------------------------------------

def _moe_node(x, p, first, count, zero=8):
    """`mx.sym.MoE` over the real experts `first` .. `first + count` of
    layer 0's 8, the router 8 + `zero` wide: (out, load)."""
    names = ["router_weight", "router_bias", "gate_weight", "down_weight",
             "up_weight"]
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    node = mx.sym.MoE(*v, num_experts=8, zero_experts=zero, hidden_size=16,
                      k=3, act_type="silu", gated=True, no_bias=True,
                      normalize=False, select_bias=True, route_scale=6.0,
                      held_first=first, held_count=count, return_load=True,
                      name="moe")
    feed = {"data": mx.nd.array(x)}
    for n in names:
        w = p["l0_" + n]
        feed[n] = mx.nd.array(w[first:first + count]
                              if n.endswith(("gate_weight", "down_weight",
                                             "up_weight")) else w)
    pred = mx.Predictor(node, feed, {"data": x.shape})
    pred.forward(data=x)
    return pred.get_output(0), pred.get_output(1)


def test_the_expert_shares_and_the_identity_term_once_make_the_layer(params):
    """Four chips of two experts: their routed parts — each share's output
    less the identity term every chip computes alike — plus the identity
    term ONCE are the uncut layer's, the program's and the reference's."""
    import jax.numpy as jnp

    x = np.random.default_rng(2).normal(size=(1, 24, 48)).astype(np.float32)
    whole, load = _moe_node(x, params, 0, 8)
    layer = [jnp.asarray(params["l0_" + n]) for n in reference.ROUTED]
    kw = dict(top_k=3, scale=6.0, zero=8, first=0)
    want, _ = reference.expert_layer(jnp.asarray(x[0]), *layer, **kw)
    _close(whole[0], want)
    identity, _ = reference.expert_layer(
        jnp.asarray(x[0]), *layer[:2], *(w[:0] for w in layer[2:]), **kw)
    assert _far(whole[0], np.asarray(want) - np.asarray(identity)) > FELT
    parts = [_moe_node(x, params, first, 2) for first in (0, 2, 4, 6)]
    routed = sum(np.asarray(out[0], np.float64) - np.asarray(identity)
                 for out, _ in parts)
    _close(routed + np.asarray(identity), whole[0])
    # the loads: each share's own experts, and the zero-compute experts'
    # pairs on every chip alike; every pair is somewhere
    assert load.shape == (9,) and load.sum() == 24 * 3
    for (_, part), first in zip(parts, (0, 2, 4, 6)):
        np.testing.assert_array_equal(part[:2], load[first:first + 2])
        assert part[2] == load[8] > 0
    # and the reference given a share computes that share
    got, _ = reference.expert_layer(
        jnp.asarray(x[0]), *layer[:2], *(w[2:4] for w in layer[2:]),
        **dict(kw, first=2))
    _close(parts[1][0][0], got)


def test_the_head_shares_attention_outputs_sum_to_the_sublayers(params,
                                                               published):
    """Two chips of two heads: what each share's attention sublayer adds
    to the stream — its heads' rows of W_qb and W_kvb, its columns of W_o,
    in the program and in the reference — sum to the four-head
    sublayer's."""
    import jax.numpy as jnp

    geo = reference.geometry(CONFIG)
    x = np.random.default_rng(3).normal(size=(20, 48)).astype(np.float32)
    names = reference.ATTENTION
    whole = reference.attention(
        jnp.asarray(x), *(jnp.asarray(published["l0_" + n]) for n in names),
        **geo)
    half = dict(CONFIG, num_attention_heads=2, held_heads=[0, 2])
    total = 0.0
    for first in (0, 2):
        rows = {"qb_weight": 12, "kvb_weight": 16}
        share = {}
        for n in names:
            w = published["l0_" + n]
            if n in rows:
                w = w[first * rows[n]:(first + 2) * rows[n]]
            elif n == "out_weight":
                w = w[:, first * 8:(first + 2) * 8]
            share[n] = w
        part = reference.attention(
            jnp.asarray(x), *(jnp.asarray(share[n]) for n in names), **geo)
        total = total + np.asarray(part, np.float64)
        # the program at the share's heads, fed the share in ITS layout:
        # one latent sublayer and nothing else of the block
        qb, kva = (np.argsort(np.argsort(r))
                   for r in layout_rows(half))
        lm = _model(half, num_layers=2, ffn_types=["dense"] * 2,
                    num_experts=0, zero_experts=0, experts_per_token=0,
                    router_bias=False, held_experts=None, route_scale=1.0,
                    layer_types=["latent_attention"] * 2)
        mixer = lm._mixers[0]
        p = mixer.params(0)
        data = mx.sym.Variable("data")
        node = mixer.full(lm._norm(data, "l0_ln1"), p, 0)
        feed = {"l0_" + n: mx.nd.array(
            share[n][qb] if n == "qb_weight" else
            share[n][kva] if n == "kva_weight" else share[n]) for n in names}
        pred = mx.Predictor(node, feed, {"data": (1,) + x.shape})
        pred.forward(data=x[None])
        _close(pred.get_output(0)[0], part)
    _close(total, whole)


# ----------------------------------------------------------------------
# the seeded faults, each refused
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_of_the_block_is_felt(fault, published, held):
    """The identity term dropped, the weights renormalised, the x 6
    missing, the selection bias dropped or added to the weights, the
    branch joined one sublayer early, either latent's rescale missing, or
    FFN[l,0] reading a norm of its own: each is far from what the program
    computes; where a `TransformerLM` argument spells the fault, the
    program so built is the reference's control."""
    got = _score(family.model(CONFIG), held, TOKENS)
    control = reference.forward(published, CONFIG, TOKENS, fault=fault)[0]
    assert _far(got, control) > FELT, fault
    if fault in PROGRAM_FAULTS:
        _close(_score(_model(**PROGRAM_FAULTS[fault]), held, TOKENS), control)


def test_both_rescales_missing_is_the_kind_without_the_option(published,
                                                              held):
    lm = _model(latent_lora_rescale=False)
    plain = dict(CONFIG, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    _close(_score(lm, held, TOKENS),
           reference.forward(published, plain, TOKENS)[0])
    assert _far(_score(family.model(CONFIG), held, TOKENS),
                reference.forward(published, plain, TOKENS)[0]) > FELT


def test_the_cells_check_passes_the_model_and_refuses_each_control(
        params, held):
    """`check_against_reference` as the cell runs it, at the tiny size,
    every slot live: the model passes; the faults, computed by the
    reference on the program's own sequences, are refused."""
    session = _session(held)
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   3, 16)
        assert ok, facts
        assert facts["logit_rel_err_worst"] < RTOL
        assert facts["rows_a_step"] == 3 and facts["compared"] > 0
        for fault in ("no_identity", "early_join", "no_kv_rescale"):
            ok, facts = family.check_against_reference(
                CONFIG, session, params, 3, 16, fault=fault)
            assert not ok and facts["refused_by"], fault
    finally:
        session.close()


def test_a_near_tie_counts_only_where_this_chip_feels_it():
    """`reference.route`'s two margins: a tie between two real experts of
    other chips, or between two zero-compute experts, is no near tie; one
    across a held expert, or between a zero-compute expert and a real one,
    is."""
    import jax.numpy as jnp

    def margins(logits, first=0, count=2):
        # a router that passes its input through: p = softmax(logits)
        w, m = reference.route(jnp.asarray([logits], jnp.float32),
                               jnp.eye(8), jnp.zeros(8), top_k=2, scale=6.0,
                               zero=3, first=first, count=count)
        return np.asarray(m)[:, 0], np.asarray(w)[0]

    # columns 0-4 real (0-1 held), 5-7 zero-compute; the edge of the
    # choice of 2 lies between the second and the third largest
    (held, zero), w = margins([0, 0, 3.0, 2.0, 1.99, -1, -1, -1])
    assert held > 0.5 and zero > 0.5      # two absent real experts tie
    assert w[2] > 0 and w[3] > 0 and w[4] == 0
    (held, zero), _ = margins([0, 0, 3.0, -1, -1, 2.0, 1.99, -1])
    assert held > 0.5 and zero > 0.5      # two zero-compute experts tie
    (held, zero), _ = margins([0, 0, 3.0, 2.0, -1, 1.99, -1, -1])
    assert held > 0.5 and zero < 0.02     # zero-compute against real
    (held, zero), _ = margins([0, 1.99, 3.0, 2.0, -1, -1, -1, -1])
    assert held < 0.02 and zero > 0.5     # a held expert at the edge


# ----------------------------------------------------------------------
# the options
# ----------------------------------------------------------------------

def test_the_new_options_say_what_they_need():
    with pytest.raises(ValueError, match="last layer"):
        _model(ffn_types=["shortcut", "dense", "dense", "shortcut"])
    with pytest.raises(ValueError, match="zero_experts"):
        TransformerLM(vocab=8, zero_experts=4)
    with pytest.raises(ValueError, match="num_experts"):
        TransformerLM(vocab=8, num_layers=2, ffn_types=["shortcut", "dense"])
    with pytest.raises(ValueError, match="latent_value_dim"):
        _model(latent_value_dim=13)           # wider than the key's 12
    # an option appears on a node only when a spec sets it
    plain = TransformerLM(vocab=8, num_experts=4, experts_per_token=2)
    assert "zero_experts" not in plain._ffns[0].attrs
    assert family.model(CONFIG)._ffns[0].attrs["zero_experts"] == 8


def test_the_batcher_books_zero_pairs_and_two_rings_a_layer(held):
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("moe.zero_pairs", "moe.routed_pairs", "moe.pairs",
             "cache.latent_bytes", "cache.reserved_bytes", "mla.layer_steps")
    session = _session(held, max_sessions=2)
    try:
        before = {n: telemetry.counter_value(n) for n in names}
        reqs = [GenerateRequest("lm", TOKENS[:n], 30.0, 6) for n in (20, 9)]
        assert session.admit(reqs) == []
        while session.active():
            session.decode_step()
        moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    finally:
        session.close()
        telemetry.set_enabled(was)
    # every pair is a held expert's or a zero-compute expert's (all 8 real
    # experts are held here), 3 a computed row a routed layer
    assert moved["moe.zero_pairs"] > 0 and moved["moe.pairs"] > 0
    assert (moved["moe.zero_pairs"] + moved["moe.pairs"]
            == moved["moe.routed_pairs"])
    # the session's state is latent rings and nothing else; four of them
    assert moved["cache.latent_bytes"] == moved["cache.reserved_bytes"] > 0
    assert moved["mla.layer_steps"] % 4 == 0


def test_the_configuration_keeps_every_published_width():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/longcat-flash-omni.json")) as f:
        config = json.load(f)
    published = {"hidden_size": 6144, "ffn_hidden_size": 12288,
                 "expert_ffn_hidden_size": 2048, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_rope_head_dim": 64,
                 "qk_nope_head_dim": 128, "v_head_dim": 128, "moe_topk": 12,
                 "zero_expert_num": 256, "router_experts": 768,
                 "routed_scaling_factor": 6, "rope_theta": 10000000,
                 "max_position_embeddings": 131072}
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted(
        ["num_layers", "num_attention_heads", "n_routed_experts",
         "vocab_size"])
    # the guide's floors: four layers, 8 experts, an eighth of the vocabulary
    assert config["num_layers"] == 4 and config["n_routed_experts"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["deployment"]["chips_per_layer"] == 64
    args = family.model_args(config)
    assert args["num_layers"] == 8 and args["num_heads"] == 8
    assert (args["num_experts"], args["zero_experts"]) == (512, 256)
    # 13.69 GB of float32 weights
    total = sum(int(np.prod(s)) for s in family.param_shapes(config).values())
    assert abs(4 * total / 1e9 - 13.69) < 0.02
