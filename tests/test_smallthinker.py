"""SmallThinker's block through `TransformerLM` and `GenerativeSession`: a
full attention layer WITHOUT a position signal over three sliding-window
layers with rotary positions, groups of SEVEN query heads a K/V head, a
head width times heads that is not the hidden size, a softmax router that
reads what the ATTENTION reads (`router_input="mixer"`: one more operand of
`mx.sym.MoE`) and keeps three of eight ReLU-gated experts
(`expert_act="relu"`), renormalised, every expert held, an untied head —
against the plain reference of the benchmark
(benchmarks/reference/smallthinker.py: float32 `jax.numpy` at "highest", no
cache, independent of `mxnet_tpu`).

Tiny widths that keep the SHAPE of the model (4 layers, hidden 48, 7 heads
of 8 over 1 K/V head, a window of 16 under buckets of 32 and 48), both
sides float32 on the CPU: errors are float32 rounding (measured 3e-7 of the
largest logit); the bound 1e-4 is far above that and a fortieth of what one
bfloat16 pass leaves.  Nothing of a layer is cut (`chips_per_layer` 1: all
64 experts and the whole vocabulary are held), so the guide's "the shares
add up to the whole layer" test has nothing to add up and is not here.  The
file costs about 60 s.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import attention
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.families import afmoe  # noqa: E402
from benchmarks.families import smallthinker as family  # noqa: E402
from benchmarks.reference import smallthinker as reference  # noqa: E402

W = 16
CONFIG = {"vocab_size": 67, "hidden_size": 48, "head_dim": 8,
          "num_attention_heads": 7, "num_key_value_heads": 1,
          "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 8,
          "moe_num_active_primary_experts": 3,
          "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
          "num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
          "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": W,
          "rope_scaling": None, "rope_theta": 1500000, "rms_norm_eps": 1e-6,
          "tie_word_embeddings": False, "max_position_embeddings": 96,
          "param_dtype": "float32"}
RTOL = 1e-4  # of the largest |logit|; see the module docstring
FELT = 1e-2  # a fault moves a logit by at least this share of the largest
# the five faults a wrong program would compute, as `TransformerLM`
# arguments that differ from the family's
FAULTS = {"router_on_ffn_input": dict(router_input="ffn"),
          "silu_gate": dict(expert_act="silu"),
          "not_renormalised": dict(route_norm=False),
          "rope_on_full": dict(positions="rotary")}
TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 67, 64)]


@pytest.fixture(scope="module")
def params():
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x10 makes every part of the block matter (the router's
    # columns stay: its logits have their standard deviation of 1.5)
    p = family.make_params(CONFIG, 5, jax.devices("cpu")[0])
    return {k: v if k.endswith(("_gamma", "_router_weight")) else 10.0 * v
            for k, v in p.items()}


def _hold(params):
    return {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}


@pytest.fixture(scope="module")
def held(params):
    return _hold(params)


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max() / np.abs(want).max())


def _far(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


def _model(**change):
    return TransformerLM(**dict(family.model_args(CONFIG), **change))


def _score(lm, held, tokens):
    t = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(held), {"data": (1, t)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(t, lm.vocab)


def _session(held, lm=None, **kw):
    kw = dict(dict(max_sessions=3, max_len=72, max_decode_tokens=40,
                   seq_buckets=[32, 48]), **kw)
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

def test_the_family_builds_the_published_shape_and_names_no_model():
    args = family.model_args(CONFIG)
    assert args["layer_types"] == ["attention"] + ["window_attention"] * 3
    assert args["positions"] == {"window_attention": "rotary"}
    assert (args["router_input"], args["expert_act"]) == ("mixer", "relu")
    assert args["route_norm"] is True and args["tied_head"] is False
    lm = family.model(CONFIG)
    assert lm.num_heads // lm.num_kv_heads == 7
    assert lm.num_heads * lm.d_head != lm.d_model
    assert lm.mixed_symbol(3) is not None      # a prompt rides the step
    spec = lm.cache_spec(4, 72)
    assert [e.shape[3] for e in spec.values()] == [72, 72] + [W] * 6
    shapes = family.param_shapes(CONFIG)
    assert set(shapes) == set(lm.score_symbol().list_arguments()) - {"data"}


def test_score_symbol_matches_the_reference(params, held):
    """The full-sequence graph over 64 positions, four windows deep."""
    _close(_score(family.model(CONFIG), held, TOKENS),
           reference.logits(params, CONFIG, TOKENS))


@pytest.mark.parametrize("prompt,bucket", [(12, 32), (17, 32), (40, 48)])
def test_prefill_then_decode_through_the_rings_matches_the_reference(
        prompt, bucket, params, held):
    """Prefill and then every decode step to position 64 against ONE full
    forward of the reference: a prompt shorter than the window (the rings
    wrap under decode steps), one a position past it, and one of 2.5
    windows through rings SHORTER than its bucket — the prefill writes the
    prompt's last 16 positions, each where a decode step would have put it
    — whose steps then cross two more wraps (positions 48 and 64)."""
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
    want = np.asarray(reference.logits(params, CONFIG, TOKENS))
    _close(np.asarray(got), want[prompt - 1:])


def test_the_batcher_with_every_slot_live_emits_the_references_tokens(
        params, held):
    """Three requests through `admit` / `decode_step` — each prompt rides
    a mixed step beside the rows already live — with every slot taken: each
    request's tokens are the reference's greedy choices along its own
    sequence (ONE forward a request, the emitted tokens teacher-forced)."""
    session = _session(held)
    try:
        reqs = [GenerateRequest("lm", TOKENS[a:a + n], 30.0, 12)
                for a, n in ((0, 40), (3, 20), (7, 33))]
        assert session.admit(reqs) == []
        assert session._mixed
        while session.active():
            session.decode_step()
    finally:
        session.close()
    for (a, n), r in zip(((0, 40), (3, 20), (7, 33)), reqs):
        tokens = list(r.future.result(timeout=5).tokens)
        assert len(tokens) == 12
        seq = TOKENS[a:a + n] + tokens
        want = np.asarray(reference.logits(params, CONFIG, seq))
        assert tokens == [int(t) for t in want[n - 1:-1].argmax(axis=-1)]


def test_an_admission_in_a_mixed_step_leaves_the_other_slots_logits_equal(
        held):
    """Slots 0 and 2 live; their step taken ALONE (the decode program) and
    as riders of the mixed step that admits a 40-position prompt into slot
    1: the same logits, and the admitted prompt's are its lone prefill's."""
    def live():
        session = _session(held)
        _prefill(session, TOKENS[:40], 48, slot=0)
        _prefill(session, TOKENS[5:25], 32, slot=2)
        return session

    data = np.asarray([[3.0], [11.0], [0.0]], np.float32)
    slot = np.asarray([0, 2, 3], np.float32)       # the third row idles
    length = np.asarray([40, 20, 0], np.float32)
    alone = live()
    try:
        exe, fn = alone._program(alone._decode_pred, 3, 1, False)
        want = alone._run(exe, fn, data, slot, length)
        lone = _prefill(alone, TOKENS[9:49], 48, slot=1)
    finally:
        alone.close()
    mixed = live()
    try:
        exe, fn = mixed._program(mixed._prefill_pred, 1, 48, True)
        prompt = np.zeros((1, 48), np.float32)
        prompt[0, :40] = TOKENS[9:49]
        small, _ = mixed._launch(
            exe, fn, mixed._state, prompt, np.ones((1,), np.float32),
            np.full((1,), 40, np.float32), logits=True,
            riders=(data, slot, length))
        got = np.asarray(small[0])
    finally:
        mixed.close()
    assert got.shape == (4, 67)
    _close(got[1:3], want[:2], 1e-5)
    _close(got[0], lone, 1e-5)


# ----------------------------------------------------------------------
# the five faults, felt
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_of_the_block_is_felt_and_is_the_references_control(
        fault, params, held):
    """A program with the router on the FFN's own input, a SiLU gate,
    weights not renormalised, or rotary on the full layer: far from the
    reference, and exactly the reference's control of that name."""
    got = _score(_model(**FAULTS[fault]), held, TOKENS)
    want = np.asarray(reference.logits(params, CONFIG, TOKENS))
    assert _far(got, want) > FELT
    _close(got, reference.forward(params, CONFIG, TOKENS, fault=fault)[0])


def test_the_windows_edge_is_felt(params, held):
    """Row t attends s <= t with t - s < 16: a window one wider, and none,
    are felt from the first row that has 16 positions behind it."""
    want = np.asarray(reference.logits(params, CONFIG, TOKENS))
    wider = _score(_model(sliding_window=W + 1), held, TOKENS)
    _close(wider[:W], want[:W])
    assert _far(wider[W:], want[W:]) > FELT
    none = np.asarray(reference.forward(params, CONFIG, TOKENS,
                                        fault="no_window")[0])
    _close(none[:W], want[:W])
    assert _far(none[W:], want[W:]) > FELT


def test_the_two_arguments_are_a_routed_ffns():
    for change in (dict(router_input="mixer"), dict(expert_act="relu")):
        with pytest.raises(ValueError, match=next(iter(change))):
            TransformerLM(vocab=8, **change)
    with pytest.raises(ValueError, match="router_input"):
        _model(router_input="attention")
    with pytest.raises(ValueError, match="expert_act"):
        _model(expert_act="gelu")


def test_the_cells_check_passes_the_model_and_refuses_each_control(
        params, held):
    """`check_against_reference` as the cell runs it, at the tiny size:
    every slot live, one prompt a bucket and more, each past the window;
    the model passes, the bfloat16 reference and each fault are refused."""
    session = _session(held)
    try:
        ok, facts = family.check_against_reference(
            CONFIG, session, params, 3,
            controls=("bfloat16",) + reference.FAULTS)
    finally:
        session.close()
    assert ok and facts["logit_rel_err_worst"] < RTOL
    assert min(facts["prompts"]) > W and facts["rows_a_step"] == 3
    assert 0 < facts["compared"] and facts["skipped_share"] < 0.7
    # the second prompt's prefill carried one live row, the third's two
    assert facts["rider_rows"] == 3
    for name, control in facts["controls"].items():
        assert control["refused_by"], (name, control)


def test_the_check_compares_the_rows_that_ride_a_prompts_prefill(
        params, held):
    """The check's prefills are the window's: the rows prefilled before
    ride the mixed step, and THEIR logits are compared — two riders' rows
    crossed in the last admission are felt, and refused."""
    session = _session(held)
    try:
        rows = family.check_rows(CONFIG, session, params, 3)
    finally:
        session.close()
    assert rows["rider"].sum() == rows["rider_rows"] == 3
    assert rows["err"][rows["rider"]].max() < RTOL
    session = _session(held)
    run = session._run

    def crossed(exe, fn, data, slot, length, riders=None):
        logits = run(exe, fn, data, slot, length, riders=riders)
        if riders is not None and (riders[2] > 0).sum() == 2:
            logits = logits[[0, 2, 1, *range(3, len(logits))]]
        return logits

    session._run = crossed
    try:
        rows = family.check_rows(CONFIG, session, params, 3)
    finally:
        session.close()
    assert rows["err"][rows["rider"]].max() > FELT
    rows["margin"][rows["rider"]] = 1.0     # whatever the margin rule skips
    ok, facts = family.judge(rows, 0.0)
    assert not ok and facts["logit_rel_err_riders"] > FELT


# ----------------------------------------------------------------------
# the router's own operand
# ----------------------------------------------------------------------

def _moe(x, tap, p, **attrs):
    names = ["router_weight", "gate_weight", "down_weight", "up_weight"]
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    feed = {"data": mx.nd.array(x)}
    if tap is not None:
        v.append(mx.sym.Variable("router_data"))
        feed["router_data"] = mx.nd.array(tap)
        attrs["router_input"] = True
    node = mx.sym.MoE(*v, num_experts=8, hidden_size=16, k=3,
                      act_type="relu", gated=True, no_bias=True,
                      normalize=True, return_load=True, **attrs)
    assert node.list_arguments()[-1] == ("router_data" if tap is not None
                                         else "up_weight")
    feed.update({n: mx.nd.array(np.asarray(p["l1_" + n])) for n in names})
    exe = node.bind(mx.cpu(), feed, grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


def test_the_routers_operand_set_to_data_is_the_node_without_it(params):
    """`mx.sym.MoE(router_input=True)` scores its LAST operand and
    multiplies `data`: handed `data` itself it is the node without the
    operand bit for bit; handed other rows it chooses by them (the
    reference's own route) while the experts still see `data`."""
    import jax

    rng = np.random.default_rng(2)
    x, tap = (rng.standard_normal((24, 48)).astype(np.float32)
              for _ in range(2))
    plain, load = _moe(x, None, params)
    same, same_load = _moe(x, x.copy(), params)
    np.testing.assert_array_equal(same, plain)
    np.testing.assert_array_equal(same_load, load)
    got, got_load = _moe(x, tap, params)
    layer = lambda n: params["l1_" + n]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        weights, _ = reference.route(tap, layer("router_weight"), 3)
        want = reference.experts(x, weights, layer("gate_weight"),
                                 layer("up_weight"), layer("down_weight"),
                                 jax.nn.relu)
    _close(got, want, 1e-5)
    assert _far(plain, want) > FELT
    np.testing.assert_array_equal(
        got_load, np.asarray((weights > 0).sum(axis=0), np.float32))
    with pytest.raises(Exception, match="router_input"):
        _moe(x, tap, params, capacity_factor=2.0)


# ----------------------------------------------------------------------
# what it counts
# ----------------------------------------------------------------------

def test_a_window_layer_books_the_blocks_its_prefill_visits():
    """`attn.band_blocks` / `attn.causal_blocks`: by `prefill_block`'s
    tiling where the TPU's kernel takes the bucket — this model's three
    window layers at the cell's widths and buckets, against the family's
    own count — and one block a layer where the `jax.numpy` body computes
    the whole square; a decode step adds none; the Trinity preset, whose
    window is its largest bucket, visits what a causal prefill would."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    for t, share in ((7168, 89.3), (8192, 83.3), (9216, 77.8),
                     (10240, 72.7)):
        rows, keys = attention.prefill_block((1, t, 3584), 28, 4, "tpu")
        assert (rows, keys) == (128, 1024)     # 1,024 // 7 rows, by 128
        booked = lm.call_counters(positions=t, platform="tpu")
        band = family.band_blocks(t, 4096, rows, keys)
        causal = family.band_blocks(t, None, rows, keys)
        assert booked["attn.band_blocks"] == 3 * band
        assert booked["attn.causal_blocks"] == 3 * causal
        assert round(100.0 * band / causal, 1) == share
        assert booked["attn.kernel_positions"] == 4 * t
        off = lm.call_counters(positions=t, platform="cpu")
        assert (off["attn.band_blocks"], off["attn.causal_blocks"]) == (3, 3)
    step = lm.call_counters(rows=8, lengths=[9000] * 8, computed=8, pages=45,
                            max_len=10752, platform="tpu")
    assert (step["attn.band_blocks"], step["attn.causal_blocks"]) == (0, 0)
    assert step["kv.wrapped_rows"] == step["kv.window_rows"] == 3 * 8
    with open(os.path.join(root, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        trinity = afmoe.model(json.load(f))
    for t in (1024, 2048):
        booked = trinity.call_counters(positions=t, platform="tpu")
        assert booked["attn.band_blocks"] == booked["attn.causal_blocks"] > 0


def test_the_batcher_books_the_new_counters_and_wrapped_rings(held):
    """Two requests past the window through `admit` / `decode_step`: every
    row-step of a window layer is wrapped, the window rings are their share
    of the reserved bytes, and each mixed step books a block a window
    layer (the CPU's body)."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("attn.band_blocks", "attn.causal_blocks", "kv.window_rows",
             "kv.wrapped_rows", "cache.window_bytes", "cache.reserved_bytes",
             "serving.prefill.mixed", "moe.routed_pairs")
    session = _session(held, max_sessions=2)
    try:
        before = {n: telemetry.counter_value(n) for n in names}
        reqs = [GenerateRequest("lm", TOKENS[:n], 30.0, 6) for n in (40, 20)]
        assert session.admit(reqs) == []
        while session.active():
            session.decode_step()
        moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    finally:
        session.close()
        telemetry.set_enabled(was)
    assert moved["serving.prefill.mixed"] >= 1   # with a row riding it
    assert moved["attn.band_blocks"] == moved["attn.causal_blocks"] == 2 * 3
    assert moved["kv.wrapped_rows"] == moved["kv.window_rows"] > 0
    # three window rings of 16 beside a full ring of 72
    assert moved["cache.window_bytes"] * (3 * W + 72) == (
        moved["cache.reserved_bytes"] * 3 * W)
    assert moved["moe.routed_pairs"] > 0
