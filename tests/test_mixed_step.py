"""PR 46: a prompt rides the decode step.  `TransformerLM.mixed_symbol` is
ONE program that prefills the admitted prompt and advances every live row;
a session whose model offers it binds it in the prefill bucket programs'
place, `admit` leaves the prompt pending and `decode_step` dispatches one
program an iteration.  Held here on the CPU: the mixed steps serve what
`prefill_symbol` + `decode_symbol` serve by hand, for every mixer and FFN
kind, token for token and cache entry for cache entry; a model with a kind
that has no mixed form keeps its two programs; and the batcher's order,
retirements, private surface and counters."""
import importlib
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

from test_transformer_lm import TwoProgramLM

VOCAB, MAX_LEN, SLOTS = 24, 32, 3
BASE = dict(vocab=VOCAB, num_layers=2, num_heads=2, d_model=16,
            max_len=MAX_LEN)
RMS = dict(BASE, norm="rms", bias=False)
# one model a mixer kind (beside a full-attention layer where the kind is
# not attention itself), the two FFN kinds among them
KINDS = {
    # OPT's block: a learned position table, biases, a tied head
    "learned_dense": dict(BASE),
    "rotary_routed": dict(RMS, num_heads=4, num_kv_heads=2,
                          positions="rotary", qk_norm=True, tied_head=False,
                          num_experts=4, experts_per_token=2),
    "nope_swiglu": dict(RMS, positions="none", ffn="swiglu"),
    # a window of 4 under prompts of 5 to 9: the rings wrap in the prefill
    # and again in the steps
    "window": dict(RMS, positions={"window_attention": "rotary"},
                   layer_types=["window_attention", "attention"],
                   sliding_window=4, qk_norm="head", out_gate=True,
                   block_norm="both"),
    "delta_rule_routed": dict(
        RMS, positions="none", layer_types=["linear_attention", "attention"],
        linear_heads=4, linear_key_heads=2, linear_key_dim=8,
        linear_value_dim=8, linear_chunk=4, linear_neg_eigval=False,
        block_norm="output", num_experts=4, experts_per_token=2,
        shared_d_ff=16, shared_gate=True, route_norm=True),
    # no mixed form: the fallback
    "mamba": dict(RMS, positions="none", ffn="swiglu",
                  layer_types=["mamba", "attention"], mamba_heads=4,
                  mamba_head_dim=8, mamba_state=8, mamba_chunk=4),
    "latent": dict(RMS, layer_types=["latent_attention"] * 2,
                   latent_q_rank=8, latent_kv_rank=8, latent_nope_dim=4,
                   latent_rope_dim=4, latent_value_dim=8, num_experts=4,
                   experts_per_token=2, shared_d_ff=16),
}
TWO_PROGRAMS = {"mamba", "latent"}
PROMPTS = [5, 9, 3, 7]     # the fourth waits for a slot
BUDGETS = [6, 4, 7, 5]
BUCKETS = [8, 16]


def _params(lm, seed):
    """Seeded weights of every parameter of `lm`'s serving graphs."""
    graph = lm.prefill_symbol()
    wire = dict(data=(1, 8), slot=(1,), length=(1,), last_token=(2,),
                **{n: e.shape for n, e in lm.cache_spec(2, MAX_LEN).items()})
    shapes, _, _ = graph.infer_shape(**wire)
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(graph.list_arguments(), shapes):
        if name in wire:
            continue
        value = 0.3 * rng.randn(*shape)
        if name.endswith("_gamma"):
            value = 1.0 + 0.3 * value
        if name.endswith("A_log"):
            value = np.abs(value)
        params[name] = mx.nd.array(value.astype(np.float32), ctx=mx.cpu())
    return params


def _session(lm, params, slots=SLOTS):
    return GenerativeSession("lm", lm, params, ctx=mx.cpu(),
                             max_sessions=slots, max_len=MAX_LEN,
                             seq_buckets=BUCKETS)


def _requests(seed, prompts=PROMPTS, budgets=BUDGETS, eos=None):
    rng = np.random.RandomState(seed)
    return [GenerateRequest("lm", rng.randint(0, VOCAB, n), 60.0, b,
                            eos_id=eos)
            for n, b in zip(prompts, budgets)]


def _drive(gs, reqs):
    """The server's loop in miniature; returns each request's slot."""
    slots, left = {}, list(reqs)
    while left or gs.active():
        if left and gs.free_slots():
            left = gs.admit(left)
            for sess in [*gs._pending, *gs._active]:
                slots.setdefault(id(sess.req), sess.slot)
        gs.decode_step()
    return [slots[id(r)] for r in reqs]


def _by_hand(gs, prompt, budget, slot):
    """One request through the session's prefill bucket program and then
    its one-row decode program, alone: the tokens."""
    bucket = min(b for b in BUCKETS if b >= len(prompt))
    exe, fn = gs._program(gs._prefill_pred, 1, bucket, True)
    data = np.zeros((1, bucket), np.float32)
    data[0, :len(prompt)] = prompt
    at = np.full((1,), slot, np.float32)
    logits = gs._run(exe, fn, data, at,
                     np.full((1,), len(prompt), np.float32))
    assert logits.shape == (1, VOCAB)
    tokens = [int(logits[0].argmax())]
    exe, fn = gs._program(gs._decode_pred, 1, 1, False)
    for step in range(budget - 1):
        logits = gs._run(exe, fn, np.asarray([[tokens[-1]]], np.float32), at,
                         np.full((1,), len(prompt) + step, np.float32))
        tokens.append(int(logits[0].argmax()))
    return tokens


def _ops(graph):
    """A graph's nodes, auto-numbered names aside: (op, inputs)."""
    return [(n["op"], n["inputs"]) for n in json.loads(graph.tojson())["nodes"]]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_mixed_steps_serve_what_prefill_and_decode_serve_by_hand(kind):
    """The same seeded requests through a session with mixed steps — four
    prompts over three slots, so prompts ride steps that carry zero, one
    and two live rows and a slot is reused — and through `prefill_symbol`
    + `decode_symbol` by hand, one request at a time into the same slots:
    every token, and every cache entry where a session wrote it (a ring's
    positions up to what was fed, the whole ring once it wrapped; a
    recurrent slot whole)."""
    lm = TransformerLM(**KINDS[kind])
    params = _params(lm, seed=sorted(KINDS).index(kind))
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    served = _session(lm, params)
    hand = _session(TwoProgramLM(**KINDS[kind]), params)
    try:
        assert served._mixed == (kind not in TWO_PROGRAMS)
        assert not hand._mixed
        if kind in TWO_PROGRAMS:
            # the fallback: no mixed graph, and the session's two
            # predictors hold the two graphs the model always had
            assert lm.mixed_symbol(SLOTS) is None
            for pred, graph in ((served._prefill_pred, lm.prefill_symbol),
                                (served._decode_pred, lm.decode_symbol)):
                assert _ops(pred._symbol) == _ops(graph())
        reqs = _requests(seed=7)
        slots = _drive(served, reqs)
        mixed = telemetry.counter_value("serving.prefill.mixed")
        riders = telemetry.counter_value("serving.prefill.rider_rows")
        if kind in TWO_PROGRAMS:
            assert (mixed, riders) == (0, 0)
            assert telemetry.counter_value("serving.decode.sessions") == 4
            assert {k for k, _ in served._programs} == {"prefill", "decode"}
            assert not any("row_data" in exe.arg_dict
                           for exe in served._programs.values())
        else:
            # all but the first prompt found a live row to carry
            assert mixed == 3 and riders >= 4
        fed = {}
        for req, slot, budget in zip(reqs, slots, BUDGETS):
            prompt = req.inputs["data"].reshape(-1).astype(int).tolist()
            want = _by_hand(hand, prompt, budget, slot)
            got = req.future.result(timeout=0)
            assert got.tokens.tolist() == want, (kind, prompt)
            assert got.finish_reason == "length"
            fed[slot] = len(prompt) + budget - 1   # the slot's last tenant
        for (name, entry), a, b in zip(served._spec.items(), served._state,
                                       hand._state):
            a, b = np.asarray(a), np.asarray(b)
            for slot, n in fed.items():
                if entry.kind == "state":
                    mine, theirs = a[slot], b[slot]
                else:
                    ring = entry.shape[3]
                    upto = ring if n >= ring else n
                    mine, theirs = a[slot][..., :upto], b[slot][..., :upto]
                assert np.abs(theirs).max() > 0, (kind, name)
                np.testing.assert_allclose(mine, theirs, rtol=2e-4,
                                           atol=2e-5, err_msg=name)
    finally:
        served.close()
        hand.close()
        telemetry.reset()
        telemetry.set_enabled(prev)


@pytest.fixture
def opt_like():
    lm = TransformerLM(**KINDS["learned_dense"])
    return lm, _params(lm, seed=11)


@pytest.fixture
def counted():
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.set_enabled(prev)


def _counters(*names):
    return [telemetry.counter_value(n) for n in names]


def test_a_session_binds_no_more_programs_than_before(opt_like):
    """The mixed step takes the prefill bucket programs' place: as many
    bound executors as the two-program session of the same model, under
    the same keys, each with every parameter in its `arg_dict`."""
    lm, params = opt_like
    mixed = _session(lm, params)
    plain = _session(TwoProgramLM(**KINDS["learned_dense"]), params)
    try:
        keys = {("prefill", t) for t in BUCKETS} | {
            ("decode", b) for b in plain._decode_ladder}
        assert mixed.warm() == plain.warm() == len(keys)
        assert set(mixed._programs) == set(plain._programs) == keys
        for exe in mixed._programs.values():
            assert set(params) <= set(exe.arg_dict)
        rows = {k: "row_data" in exe.arg_dict
                for k, exe in mixed._programs.items()}
        assert rows == {k: k[0] == "prefill" for k in rows}
    finally:
        mixed.close()
        plain.close()


def test_a_prompt_with_no_row_live_is_dispatched_by_the_next_step(
        opt_like, counted):
    """`admit` takes the slot and dispatches nothing; the `decode_step`
    that follows it in the server's loop dispatches the prompt's mixed
    step at once, with every row idle: it IS the prefill, and counts as
    no decode dispatch."""
    lm, params = opt_like
    gs = _session(lm, params)
    try:
        (req,) = _requests(seed=1, prompts=[5], budgets=[3])
        assert gs.admit([req]) == [] and gs.free_slots() == SLOTS - 1
        assert gs.active() and not gs._flights and len(gs._pending) == 1
        assert gs.decode_step() == 0
        (flight,) = gs._flights
        assert (flight.prog.kind, flight.prog.bucket, flight.riders) == (
            "prefill", 8, 0)
        assert not gs._pending and len(gs._active) == 1
        assert _counters("serving.decode.sessions", "serving.prefill.mixed",
                         "serving.prefill.rider_rows",
                         "serving.decode.dispatches",
                         "serving.prefill.bucket_positions") == [
            1, 0, 0, 0, 8]
        while gs.active():
            gs.decode_step()
        assert len(req.future.result(timeout=0).tokens) == 3
    finally:
        gs.close()


def test_pending_prompts_ride_consecutive_steps_and_earlier_rows_advance(
        opt_like, counted):
    """Three prompts admitted at once ride three consecutive iterations,
    one each, in order; the sessions admitted earlier advance a token in
    every one of them."""
    lm, params = opt_like
    gs = _session(lm, params)
    try:
        reqs = _requests(seed=2, prompts=[5, 9, 3], budgets=[8, 8, 8])
        assert gs.admit(reqs) == []
        assert [s.req for s in gs._pending] == reqs
        for k in range(3):
            assert gs.decode_step() == k
            (flight,) = gs._flights
            assert flight.prog.kind == "prefill" and flight.riders == k
            assert [s.req for s in flight.rows] == [reqs[k], *reqs[:k]]
            # positions fed: the prompt's, and one a step ridden since
            assert [s.fed - s.prompt_len for s in gs._active] == list(
                range(k, -1, -1))
        assert gs.decode_step() == 3 and gs._flights[0].prog.kind == "decode"
        assert _counters("serving.prefill.mixed",
                         "serving.prefill.rider_rows",
                         "serving.decode.dispatches") == [2, 3, 3]
        while gs.active():
            gs.decode_step()
        for r in reqs:
            assert len(r.future.result(timeout=0).tokens) == 8
        # a first token each from the prefills, the rest from decode rows
        assert telemetry.counter_value("serving.decode.tokens") == 3 * 7
    finally:
        gs.close()


@pytest.mark.parametrize("how", ["budget", "eos"])
def test_a_row_that_ends_inside_a_mixed_step_retires(opt_like, counted, how):
    """The first session's LAST row rides the second prompt's mixed step
    — its budget of two, or the token that row samples being its EOS —
    and it retires when that step is read, its slot free for the next."""
    lm, params = opt_like
    plain = _session(TwoProgramLM(**KINDS["learned_dense"]), params)
    try:
        # a first prompt whose second token is not its first again
        for seed in range(3, 40):
            first, second = _requests(seed=seed, prompts=[5, 9],
                                      budgets=[2, 4])
            want = _by_hand(plain, first.inputs["data"].astype(int).tolist(),
                            2, 0)
            if want[0] != want[1]:
                break
    finally:
        plain.close()
    assert want[0] != want[1]
    if how == "eos":
        first.max_new_tokens, first.eos_id = 6, want[1]
    gs = _session(lm, params)
    try:
        assert gs.admit([first]) == []
        gs.decode_step()
        assert gs.admit([second]) == []
        gs.decode_step()    # the second's mixed step carries the first's row
        assert gs._flights[0].riders == 1 and not first.future.done()
        gs.decode_step()    # and is read here
        out = first.future.result(timeout=0)
        assert out.tokens.tolist() == want
        assert out.finish_reason == ("length" if how == "budget" else "eos")
        assert gs.free_slots() == SLOTS - 1
        while gs.active():
            gs.decode_step()
        assert len(second.future.result(timeout=0).tokens) == 4
        # an EOS is known a step late: the row packed meanwhile is dropped
        assert telemetry.counter_value("serving.decode.dropped_rows") == (
            how == "eos")
    finally:
        gs.close()


def test_the_prefill_surface_reaches_the_mixed_program(opt_like):
    """What the benchmark's families and `chip_smoke.py` call:
    `_program(_prefill_pred, 1, T, True)` then `_run(exe, fn, data (1, T),
    slot, length)` runs the program the window runs — the mixed step, its
    rows idle — and returns the prompt's ``(1, vocab)`` logits, those of
    the full forward at the prompt's tail, with the prompt's K/V in the
    slot of the caller's choosing and no other slot touched."""
    lm, params = opt_like
    gs = _session(lm, params)
    try:
        exe, fn = gs._program(gs._prefill_pred, 1, 16, True)
        assert "row_data" in exe.arg_dict and gs._programs["prefill", 16] is exe
        prompt = np.random.RandomState(5).randint(0, VOCAB, 11)
        data = np.zeros((1, 16), np.float32)
        data[0, :11] = prompt
        logits = gs._run(exe, fn, data, np.full((1,), 1, np.float32),
                         np.full((1,), 11, np.float32))
        assert logits.shape == (1, VOCAB)
        pred = mx.Predictor(lm.score_symbol(), dict(params), {"data": (1, 11)})
        pred.forward(data=prompt[None].astype(np.float32))
        want = pred.get_output(0).reshape(11, VOCAB)[-1]
        pred.close()
        np.testing.assert_allclose(logits[0], want, rtol=1e-4, atol=1e-5)
        rings = [np.asarray(a) for a in gs._state[:-1]]
        assert all(np.abs(r[1, ..., :11]).max() > 0 for r in rings)
        assert all(np.abs(r[[0, 2]]).max() == 0 for r in rings)
    finally:
        gs.close()


def test_the_counters_after_a_scripted_sequence(opt_like, counted):
    """Two prompts a step apart, budgets 3 and 2: the first prompt's step
    carries nothing, the second's the first session's first row; then one
    plain step of both sessions' last rows."""
    lm, params = opt_like
    gs = _session(lm, params)
    try:
        first, second = _requests(seed=4, prompts=[5, 9], budgets=[3, 2])
        assert gs.admit([first]) == []
        gs.decode_step()
        assert gs.admit([second]) == []
        while gs.active():
            gs.decode_step()
        names = ("serving.decode.sessions", "serving.prefill.mixed",
                 "serving.prefill.rider_rows", "serving.decode.dispatches",
                 "serving.decode.tokens", "serving.decode.runahead_steps",
                 "serving.prefill.bucket_positions",
                 "serving.prefill.pad_positions", "serving.device.flights",
                 "kv.page_positions")
        assert dict(zip(names, _counters(*names))) == {
            "serving.decode.sessions": 2, "serving.prefill.mixed": 1,
            "serving.prefill.rider_rows": 1, "serving.decode.dispatches": 2,
            "serving.decode.tokens": 3, "serving.decode.runahead_steps": 2,
            "serving.prefill.bucket_positions": 8 + 16,
            "serving.prefill.pad_positions": 3 + 7,
            "serving.device.flights": 3, "kv.page_positions": 3 * MAX_LEN}
    finally:
        gs.close()


def test_a_mixed_step_that_cannot_be_dispatched_fails_its_request_only(
        opt_like):
    """The prompt's program cannot be had: its request fails, its slot is
    free again, and the live row it would have carried gets its plain
    step in the same iteration."""
    lm, params = opt_like
    gs = _session(lm, params)
    try:
        first, second = _requests(seed=6, prompts=[5, 9], budgets=[4, 4])
        assert gs.admit([first]) == []
        gs.decode_step()
        program = gs._program

        def broken(pred, batch, seq, prefill):
            if prefill and seq == 16:
                raise RuntimeError("no such program")
            return program(pred, batch, seq, prefill)

        gs._program = broken
        assert gs.admit([second]) == []
        assert gs.decode_step() == 1
        with pytest.raises(RuntimeError, match="no such program"):
            second.future.result(timeout=0)
        assert gs._flights[0].prog.kind == "decode"
        assert gs.free_slots() == SLOTS - 1
        while gs.active():
            gs.decode_step()
        assert len(first.future.result(timeout=0).tokens) == 4
    finally:
        gs.close()


@pytest.mark.parametrize("warmed", [False, True])
def test_the_decode_ladder_is_built_before_the_first_mixed_step(
        opt_like, counted, warmed):
    """The benchmark's warm-up traffic on two slots, budgets 2 and 4: the
    first session's only decode row rides the second prompt's step, so the
    two are never live together in a plain step — and the 2-row program
    would compile under the first window that fills both slots.  The
    session builds its ladder itself before its first mixed step, once,
    with idle rows on the live state; a session `warm()` has warmed builds
    nothing more."""
    lm, params = opt_like
    gs = _session(lm, params, slots=2)
    try:
        if warmed:
            gs.warm()
        calls = []
        call = gs._call
        gs._call = lambda *a: calls.append(a[3].shape) or call(*a)
        reqs = _requests(seed=8, prompts=[8, 16], budgets=[2, 4])
        assert gs.admit(reqs) == []
        assert gs.decode_step() == 0
        assert calls == ([] if warmed else [(1, 1), (2, 1)])
        assert {("decode", 1), ("decode", 2)} <= set(gs._programs)
        built = telemetry.counter_value("serving.decode.bucket_programs")
        rows = []
        while gs.active():
            rows.append(gs.decode_step())
        assert max(rows) == 1 and len(calls) == (0 if warmed else 2)
        # the second prompt's bucket, unless `warm` has built that too
        assert telemetry.counter_value(
            "serving.decode.bucket_programs") == built + (not warmed)
        assert [len(r.future.result(timeout=0).tokens) for r in reqs] == [
            2, 4]
    finally:
        gs.close()


# the benchmark's configurations: which models ride (ISSUE 46)
CELL_MODELS = {"opt-1.3b": True, "olmoe-1b-7b": True, "olmo-hybrid-7b": True,
               "trinity-mini": True, "qwen3-next-80b-a3b": True,
               "granite-4.0-h-micro": False, "mistral-small-4-119b": False}


@pytest.mark.parametrize("config_name", sorted(CELL_MODELS))
def test_which_of_the_benchmarks_models_have_a_mixed_step(config_name):
    """The selection is made from the layer kinds a model holds: a model
    with a Mamba-2 or a latent-attention layer offers no mixed graph (its
    session keeps `prefill_symbol` + `decode_symbol`), every other one of
    the benchmark's decoders does, with the riders' three operands."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    lm = family.model(config)
    graph = lm.mixed_symbol(8)
    kinds = {type(m).__name__ for m in lm._mixers}
    assert (graph is not None) == CELL_MODELS[config_name]
    assert CELL_MODELS[config_name] != bool(
        kinds & {"_Mamba2", "_LatentAttention"})
    if graph is not None:
        assert {"row_data", "row_slot", "row_length"} <= set(
            graph.list_arguments())
