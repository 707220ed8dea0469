"""Transformer LM workload: training through BucketingModule and the
KV-cache decode-session numerics (ROADMAP item 2; docs/serving.md
"Decode sessions & continuous batching", docs/perf.md "KV-cache
decode").

The decode pins are the acceptance criteria of the KV-cache PR:

* per-step LOGITS parity — prefill + cached decode must reproduce the
  full-recompute forward's next-token logits at EVERY step, not just
  the argmax;
* join/leave parity — a session decoding in a mixed, continuously
  re-packed batch must produce EXACTLY the tokens it produces decoding
  alone (padded rows and slot reuse may not leak across sessions);
* compile-once-per-bucket — the telemetry program counters stay flat
  across any admit/retire mix after warmup;
* zero lost futures — close(drain=False) mid-window resolves every
  submitted generation, active or queued.
"""
import contextlib
import hashlib
import importlib
import json
import os
import sys
import threading
import time
from unittest import mock

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerateRequest, GenerativeSession, ServerClosed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)   # benchmarks/: the families the cells build


class TwoProgramLM(TransformerLM):
    """A model that offers no mixed step (as one with a latent-attention
    layer offers none): its session keeps a prefill program of its own
    beside the decode ladder, dispatched at admission — the batcher every
    model had before PR 46."""

    mixed_symbol = None


def _lm_and_params(vocab=24, num_layers=2, num_heads=2, d_model=16,
                   max_len=32, seed=0, two_programs=False):
    """A tiny TransformerLM plus a randomly-initialized checkpoint in
    the plain-name form GenerativeSession consumes (arg+aux merged).
    `two_programs`: the same model without a mixed step."""
    lm = (TwoProgramLM if two_programs else TransformerLM)(
        vocab=vocab, num_layers=num_layers, num_heads=num_heads,
        d_model=d_model, max_len=max_len)
    mx.random.seed(seed)
    mod = mx.mod.Module(lm.training_symbol(), data_names=("data",),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2, 8))])
    mod.init_params(mx.init.Xavier(magnitude=2.0))
    arg, aux = mod.get_params()
    params = dict(arg)
    params.update(aux)
    return lm, params


def _score_logits(lm, params, tokens):
    """Full-recompute reference: per-position logits ``(T, vocab)`` of
    one forward over the whole prefix (the honest baseline the cached
    path must reproduce)."""
    T = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(params), {"data": (1, T)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(T, lm.vocab)


def _greedy_reference(lm, params, prompt, max_new, eos_id=None):
    """Greedy generation by full recompute — the token-level oracle."""
    toks = list(prompt)
    out = []
    for _ in range(max_new):
        nxt = int(np.argmax(_score_logits(lm, params, toks)[-1]))
        out.append(nxt)
        toks.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
        if len(toks) >= lm.max_len:
            break
    return out


def _drive(gs, reqs):
    """The server loop in miniature: admit what fits, decode one
    token-level step, re-offer the leftovers — until every request
    retires.  Returns results in submission order."""
    pending = list(reqs)
    while pending or gs.active():
        pending = gs.admit(pending)
        gs.decode_step()
    return [r.future.result(timeout=0) for r in reqs]


# ----------------------------------------------------------------------
# numerics: the cached path reproduces the full recompute
# ----------------------------------------------------------------------
def test_kv_decode_logits_match_full_recompute_every_step():
    """Prefill writes the prompt's K/V into the ring and emits the
    tail logits; every decode step then extends the cache by one
    position.  At EVERY step the logits must be allclose to a full
    forward over the entire prefix — the invariant that makes the
    speedup free."""
    lm, params = _lm_and_params()
    gs = GenerativeSession("lm", lm, params, max_sessions=1,
                           max_len=lm.max_len, seq_buckets=[8])
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, lm.vocab, size=5).tolist()
    n = len(prompt)

    # prefill through the 8-wide bucket (3 pad positions): logits must
    # come from the TRUE tail, not the pad
    exe, fn = gs._program(gs._prefill_pred, 1, 8, True)
    data = np.zeros((1, 8), np.float32)
    data[0, :n] = prompt
    logits = gs._run(exe, fn, data, np.zeros((1,), np.float32),
                     np.full((1,), n, np.float32))
    ref = _score_logits(lm, params, prompt)
    np.testing.assert_allclose(logits[0], ref[n - 1], rtol=1e-4, atol=1e-5)

    # decode step-by-step: feed the greedy token, compare against the
    # full recompute of the grown prefix at every single position
    toks = list(prompt)
    exe, fn = gs._program(gs._decode_pred, 1, 1, False)
    for step in range(8):
        nxt = int(np.argmax(logits[0]))
        toks.append(nxt)
        logits = gs._run(exe, fn, np.asarray([[nxt]], np.float32),
                         np.zeros((1,), np.float32),
                         np.full((1,), len(toks) - 1, np.float32))
        ref = _score_logits(lm, params, toks)
        np.testing.assert_allclose(
            logits[0], ref[-1], rtol=1e-4, atol=1e-5,
            err_msg="decode step %d diverged from full recompute" % step)


def test_session_tokens_match_greedy_reference():
    """End-to-end through admit()/decode_step(): greedy tokens,
    finish_reason, and prompt_len all match the full-recompute
    oracle — including EOS cut-off."""
    lm, params = _lm_and_params(seed=3)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, lm.vocab, size=rng.randint(2, 7)).tolist()
               for _ in range(5)]
    eos = 3
    gs = GenerativeSession("lm", lm, params, max_sessions=4,
                           max_len=lm.max_len, eos_id=eos,
                           seq_buckets=[8])
    reqs = [GenerateRequest("lm", p, 60.0, 6, eos_id=eos)
            for p in prompts]
    results = _drive(gs, reqs)
    for p, r in zip(prompts, results):
        want = _greedy_reference(lm, params, p, 6, eos_id=eos)
        assert r.tokens.tolist() == want, (p, r.tokens.tolist(), want)
        assert r.prompt_len == len(p)
        assert r.finish_reason == ("eos" if want[-1] == eos else "length")


def test_join_leave_mid_batch_matches_solo_decode():
    """Continuous batching parity: sessions joining (admitted while
    others are mid-decode) and leaving (retiring mid-window on
    different budgets) must each produce EXACTLY the token sequence
    they produce decoding ALONE.  Slot reuse after retirement and the
    scratch-slot padded rows may not perturb any survivor."""
    lm, params = _lm_and_params(seed=5)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, lm.vocab, size=rng.randint(2, 8)).tolist()
               for _ in range(6)]
    budgets = [3, 9, 5, 8, 2, 7]  # staggered retirement by design

    solo = []
    for p, b in zip(prompts, budgets):
        gs = GenerativeSession("lm", lm, params, max_sessions=1,
                               max_len=lm.max_len, seq_buckets=[8])
        (r,) = _drive(gs, [GenerateRequest("lm", p, 60.0, b)])
        solo.append(r.tokens.tolist())

    # mixed run: 2 KV slots for 6 requests forces queueing — each
    # retirement frees a slot that the next prompt prefills into while
    # the survivor keeps decoding (the join/leave path under test)
    gs = GenerativeSession("lm", lm, params, max_sessions=2,
                           max_len=lm.max_len, seq_buckets=[8])
    reqs = [GenerateRequest("lm", p, 60.0, b)
            for p, b in zip(prompts, budgets)]
    mixed = _drive(gs, reqs)
    for i, (r, want) in enumerate(zip(mixed, solo)):
        assert r.tokens.tolist() == want, (i, r.tokens.tolist(), want)


# ----------------------------------------------------------------------
# the loop one step ahead of the host (serving/decode.py): the token a
# row needs is on the device, so nothing below may depend on when the
# host reads it
# ----------------------------------------------------------------------
RUNAHEAD_BUDGETS = [3, 9, 1, 5, 8, 2, 7]  # staggered retirement, a budget of one


def _stream_requests(prompts, budgets, eos_ids=None):
    """Requests whose `on_token` keeps what it was called with, in
    order: (requests, one list of streamed tokens a request)."""
    streams = [[] for _ in prompts]
    reqs = [GenerateRequest("lm", p, 60.0, b, on_token=s.append,
                            eos_id=None if eos_ids is None else eos_ids[i])
            for i, (p, b, s) in enumerate(zip(prompts, budgets, streams))]
    return reqs, streams


def assert_runahead_matches_one_at_a_time(lm, params, seq_bucket=8):
    """N concurrent requests with mixed budgets through 3 slots —
    retirements mid-run, admissions into freed slots, more requests
    than slots — give the tokens, `finish_reason` and `on_token` order
    of one request at a time decoded greedily through `score_symbol`.
    (tests/test_olmoe.py runs this on a routed model.)"""
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, lm.vocab, size=rng.randint(2, 8)).tolist()
               for _ in RUNAHEAD_BUDGETS]
    telemetry.set_enabled(True)
    ahead0 = telemetry.counter_value("serving.decode.runahead_steps")
    steps0 = telemetry.counter_value("serving.decode.dispatches")
    gs = GenerativeSession("lm", lm, params, max_sessions=3,
                           max_len=lm.max_len, seq_buckets=[seq_bucket])
    try:
        reqs, streams = _stream_requests(prompts, RUNAHEAD_BUDGETS)
        results = _drive(gs, reqs)
        assert gs.free_slots() == 3 and not gs._flights
    finally:
        gs.close()
    for p, b, r, streamed in zip(prompts, RUNAHEAD_BUDGETS, results,
                                 streams):
        want = _greedy_reference(lm, params, p, b)
        assert r.tokens.tolist() == want, (p, r.tokens.tolist(), want)
        assert streamed == want
        assert r.finish_reason == "length" and r.prompt_len == len(p)
    # all but the steps that found no token in flight ran ahead
    ahead = telemetry.counter_value("serving.decode.runahead_steps") - ahead0
    steps = telemetry.counter_value("serving.decode.dispatches") - steps0
    assert 0 < ahead <= steps


def test_runahead_matches_one_at_a_time_greedy_decode():
    lm, params = _lm_and_params(seed=8)
    assert_runahead_matches_one_at_a_time(lm, params)


@pytest.mark.parametrize("two_programs", [True, False])
@pytest.mark.parametrize("budgets", [[4], [3, 6, 2, 5]])
def test_every_dispatched_flight_is_counted_once_when_it_lands(
        budgets, two_programs):
    """`serving.device.flights` is the prefills plus the decode steps
    dispatched — a mixed step that carried a live row is ONE flight that
    is both —, whatever the fences saw of them: on a real (CPU) session
    most return at once, so few flights are SEEN, never more than
    landed, and what the means divide by grows with every flight."""
    lm, params = _lm_and_params(seed=5, two_programs=two_programs)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, lm.vocab, size=4).tolist() for _ in budgets]
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    gs = GenerativeSession("lm", lm, params, max_sessions=2,
                           max_len=lm.max_len, seq_buckets=[8])
    try:
        _drive(gs, [GenerateRequest("lm", p, 60.0, b)
                    for p, b in zip(prompts, budgets)])
        steps = telemetry.counter_value("serving.decode.dispatches")
        flights = telemetry.counter_value("serving.device.flights")
        mixed = telemetry.counter_value("serving.prefill.mixed")
        # a later prompt finds the other slot's session live, unless
        # that session's last row is in flight already
        assert mixed == 0 if two_programs or len(budgets) == 1 \
            else 0 < mixed < len(budgets)
        assert flights == len(budgets) + steps - mixed == gs._seq
        assert steps >= max(budgets) - 1
        seen = telemetry.counter_value("serving.device.seen_flights")
        assert 0 <= seen <= flights
        hists = telemetry.snapshot()["histograms"]
        assert seen == sum(h["count"] for name, h in hists.items() if name in (
            "serving.device.decode_seconds", "serving.device.prefill_seconds"))
        assert telemetry.counter_value("serving.device.decode_seen") > 0
        assert telemetry.counter_value("serving.device.prefill_positions") > 0
    finally:
        gs.close()
        telemetry.reset()
        telemetry.set_enabled(prev)


def test_eos_mid_run_drops_the_row_in_flight_and_the_slot_serves_on():
    """EOS needs the token's value, which the host reads one step late:
    the session's next row is in flight by then.  Its token is dropped
    (counted once), the request ends AT the EOS token, and the slot's
    next tenant — the row's K/V landed in its slot — decodes what it
    decodes alone."""
    lm, params = _lm_and_params(seed=3)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, lm.vocab, size=n).tolist() for n in (4, 6)]
    free = [_greedy_reference(lm, params, p, 8) for p in prompts]
    # the first request's EOS: a token it samples mid-run, not before
    cut = next(i for i in range(2, 7) if free[0][i] not in free[0][:i])
    telemetry.set_enabled(True)
    dropped0 = telemetry.counter_value("serving.decode.dropped_rows")
    gs = GenerativeSession("lm", lm, params, max_sessions=1,
                           max_len=lm.max_len, seq_buckets=[8])
    try:
        reqs, streams = _stream_requests(prompts, [8, 8],
                                         eos_ids=[free[0][cut], None])
        first, second = _drive(gs, reqs)
    finally:
        gs.close()
    assert first.tokens.tolist() == streams[0] == free[0][:cut + 1]
    assert first.finish_reason == "eos"
    assert telemetry.counter_value(
        "serving.decode.dropped_rows") - dropped0 == 1
    assert second.tokens.tolist() == streams[1] == free[1]
    assert second.finish_reason == "length"


@pytest.mark.parametrize("two_programs,sampled", [
    # two prefills at admission, then three steps of both rows
    (True, (4, 4)),
    # the first prompt alone, the second with the first's row riding,
    # then one step of both rows
    (False, (3, 2))])
def test_finish_all_with_a_step_in_flight_keeps_the_tokens_computed(
        two_programs, sampled):
    """close(drain=False) between a dispatch and its read: every future
    resolves 'closed', and with every token the device had sampled."""
    lm, params = _lm_and_params(seed=4, two_programs=two_programs)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, lm.vocab, size=4).tolist() for _ in range(2)]
    gs = GenerativeSession("lm", lm, params, max_sessions=2,
                           max_len=lm.max_len, seq_buckets=[8])
    reqs = [GenerateRequest("lm", p, 60.0, 20) for p in prompts]
    assert gs.admit(reqs) == []
    for _ in range(3):
        gs.decode_step()
    (flight,) = gs._flights
    assert flight.prog.kind == "decode" and len(flight.rows) == 2
    gs.close()
    assert not gs._flights and gs.free_slots() == 2
    for p, r, n in zip(prompts, reqs, sampled):
        out = r.future.result(timeout=0)
        assert out.finish_reason == "closed"
        # the prefill's token and one a dispatched row
        assert out.tokens.tolist() == _greedy_reference(lm, params, p, n)


def test_a_failing_step_with_one_in_flight_loses_no_future():
    """A decode step that cannot be dispatched fails the sessions it
    would have served — the one in flight included — and nothing else:
    the queued request behind them is served, correctly, by the same
    tenant."""
    lm, params = _lm_and_params(seed=6)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, lm.vocab, size=4).tolist() for _ in range(3)]
    server = mx.serving.ModelServer({}, wait_ms=1.0)
    try:
        gs = server.add_generative_tenant(
            "lm", lm, params, max_sessions=2, max_len=lm.max_len,
            seq_buckets=[8])
        server.warmup()
        launch, tripped = gs._launch, []

        def flaky(exe, fn, state, data, slot, length, logits, **riders):
            # the first step that packs both sessions with both tokens
            # still on the device: their previous step is in flight
            if not tripped and data.shape == (2, 1) and (data < 0).all():
                tripped.append(1)
                raise RuntimeError("injected step failure")
            return launch(exe, fn, state, data, slot, length, logits,
                          **riders)

        gs._launch = flaky
        futs = [server.submit_generate("lm", p, max_new_tokens=12)
                for p in prompts]
        for f in futs[:2]:
            with pytest.raises(RuntimeError, match="injected"):
                f.result(timeout=60)
        third = futs[2].result(timeout=60)
        assert third.tokens.tolist() == _greedy_reference(
            lm, params, prompts[2], 12)
        assert gs.free_slots() == 2 and not gs._flights
    finally:
        server.close()


def test_run_still_returns_host_logits_of_the_served_tokens():
    """The synchronous path others reach for logits (the benchmark's
    reference check, chip_smoke.py): `_run` takes host token ids and
    returns host logits ``(B, vocab)`` whose argmax is what the batcher
    serves for the same prompt."""
    lm, params = _lm_and_params(seed=2)
    prompt = [5, 9, 3, 7]
    gs = GenerativeSession("lm", lm, params, max_sessions=2,
                           max_len=lm.max_len, seq_buckets=[8])
    try:
        (served,) = _drive(gs, [GenerateRequest("lm", prompt, 60.0, 6)])
        exe, fn = gs._program(gs._prefill_pred, 1, 8, True)
        data = np.zeros((1, 8), np.float32)
        data[0, :4] = prompt
        logits = gs._run(exe, fn, data, np.zeros((1,), np.float32),
                         np.full((1,), 4, np.float32))
        assert isinstance(logits, np.ndarray)
        assert logits.shape == (1, lm.vocab)
        toks = [int(np.argmax(logits[0]))]
        exe, fn = gs._program(gs._decode_pred, 1, 1, False)
        for i in range(5):
            logits = gs._run(exe, fn, np.asarray([[toks[-1]]], np.float32),
                             np.zeros((1,), np.float32),
                             np.full((1,), 4 + i, np.float32))
            assert logits.shape == (1, lm.vocab)
            toks.append(int(np.argmax(logits[0])))
    finally:
        gs.close()
    assert toks == served.tokens.tolist()


# ----------------------------------------------------------------------
# compile-once and the telemetry surface
# ----------------------------------------------------------------------
def test_decode_compiles_once_per_bucket():
    """warm() builds one program per prefill sequence bucket plus one
    per decode batch bucket; any admit/retire mix after that reuses
    them — zero new programs, zero executor compile misses."""
    telemetry.set_enabled(True)
    telemetry.reset()
    lm, params = _lm_and_params(seed=9)
    gs = GenerativeSession("lm", lm, params, max_sessions=4,
                           max_len=lm.max_len, seq_buckets=[4, 8])
    # decode ladder for 4 slots: [1, 2, 4]
    assert gs.warm() == 2 + 3
    progs0 = telemetry.counter_value("serving.decode.bucket_programs")
    assert progs0 == 5
    miss0 = telemetry.counter_value("executor.compile_cache_misses")

    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, lm.vocab, size=rng.randint(2, 8)).tolist()
               for _ in range(7)]
    reqs = [GenerateRequest("lm", p, 60.0, 2 + (i % 4))
            for i, p in enumerate(prompts)]
    _drive(gs, reqs)
    assert telemetry.counter_value(
        "serving.decode.bucket_programs") == progs0
    assert telemetry.counter_value(
        "executor.compile_cache_misses") == miss0
    # the loop's own instrumentation saw the run
    snap = telemetry.snapshot()
    assert snap["counters"]["serving.decode.dispatches"] > 0
    assert snap["counters"]["serving.decode.retired"] == len(reqs)
    assert snap["counters"]["serving.decode.sessions"] == len(reqs)
    assert "serving.decode.step_seconds" in snap["histograms"]
    assert "serving.prefill_seconds" in snap["histograms"]
    assert snap["gauges"]["kv.ring_bytes"] > 0
    assert snap["gauges"]["kv.slot_occupancy"] == 0.0  # all retired


def test_generate_validation_and_classic_submit_rejected():
    lm, params = _lm_and_params()
    server = mx.serving.ModelServer({})
    try:
        server.add_generative_tenant("lm", lm, params, max_sessions=2,
                                     max_len=16, seq_buckets=[8])
        # a classic submit against a generative tenant is a client bug
        with pytest.raises(MXNetError, match="generative"):
            server.submit("lm", {"data": np.zeros(4, np.float32)})
        with pytest.raises(MXNetError, match="empty prompt"):
            server.submit_generate("lm", [])
        with pytest.raises(MXNetError, match="max_new_tokens"):
            server.submit_generate("lm", [1, 2], max_new_tokens=0)
        # prompt + budget must fit the KV ring — rejected at submit,
        # not discovered mid-decode
        with pytest.raises(MXNetError, match="KV ring"):
            server.submit_generate("lm", [1] * 10, max_new_tokens=10)
    finally:
        server.close()


def test_close_no_drain_resolves_every_generation_future():
    """Zero lost futures on mid-window shutdown: with 2 KV slots and 6
    outstanding generations (some active mid-decode, some queued),
    close(drain=False) must resolve EVERY future — partial tokens with
    finish_reason='closed' for active sessions, ServerClosed for the
    still-queued ones.  Nothing hangs, nothing leaks."""
    lm, params = _lm_and_params(seed=4)
    server = mx.serving.ModelServer({}, wait_ms=1.0)
    futs = []
    try:
        server.add_generative_tenant("lm", lm, params, max_sessions=2,
                                     max_len=lm.max_len, seq_buckets=[8])
        rng = np.random.RandomState(2)
        for _ in range(6):
            prompt = rng.randint(0, lm.vocab, size=4).tolist()
            futs.append(server.submit_generate("lm", prompt,
                                               max_new_tokens=20))
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if server.stats()["generative"]["lm"]["active_sessions"] >= 1:
                break
            time.sleep(0.001)
        else:
            pytest.fail("no session went active")
    finally:
        server.close(drain=False)
    resolved = 0
    for f in futs:
        assert f.done(), "close() returned with an unresolved future"
        try:
            r = f.result(timeout=0)
        except ServerClosed:
            resolved += 1  # still queued at shutdown — failed, not lost
        else:
            resolved += 1
            assert r.finish_reason in ("closed", "length", "eos")
            assert len(r.tokens) >= 1  # prefill emitted at least one
    assert resolved == len(futs)


def test_admission_control_requeues_when_slots_full():
    """More prompts than KV slots: admit() returns the overflow
    instead of failing it, and the returned requests complete once
    retirement frees slots (the decode-window re-offer)."""
    lm, params = _lm_and_params(seed=6)
    gs = GenerativeSession("lm", lm, params, max_sessions=2,
                           max_len=lm.max_len, seq_buckets=[8])
    rng = np.random.RandomState(3)
    reqs = [GenerateRequest(
        "lm", rng.randint(0, lm.vocab, size=3).tolist(), 60.0, 4)
        for _ in range(5)]
    leftovers = gs.admit(reqs)
    assert len(leftovers) == 3 and gs.free_slots() == 0
    results = _drive(gs, leftovers)
    while gs.active():
        gs.decode_step()
    for r in reqs:
        out = r.future.result(timeout=0)
        assert len(out.tokens) == 4 and out.finish_reason == "length"
    assert gs.free_slots() == 2
    assert len(results) == 3


# ----------------------------------------------------------------------
# training: the first transformer rows
# ----------------------------------------------------------------------
def test_transformer_trains_through_bucketing_module():
    """The tentpole training pin: TransformerLM.sym_gen drives a
    BucketingModule over variable-length sequences (two buckets, pad
    label ignored) and the perplexity collapses on a deterministic
    next-token language — the same recipe that produced the
    BENCH_TABLE transformer training row."""
    from mxnet_tpu import rnn

    rng = np.random.RandomState(0)
    V, B = 30, 16
    sents = []
    for _ in range(200):
        n = rng.randint(4, 12)
        s = [int(rng.randint(2, V))]
        for _ in range(n - 1):
            s.append((s[-1] * 7 + 3) % (V - 2) + 2)
        sents.append(s)
    it = rnn.BucketSentenceIter(sents, B, buckets=[8, 12], invalid_label=0)
    lm = TransformerLM(vocab=V, num_layers=2, num_heads=2, d_model=32,
                       max_len=16)
    mod = mx.mod.BucketingModule(
        sym_gen=lm.sym_gen(invalid_label=0),
        default_bucket_key=it.default_bucket_key, context=mx.cpu())
    metric = mx.metric.Perplexity(0)

    def epoch():
        metric.reset()
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            mod.update_metric(metric, batch.label)
        return metric.get()[1]

    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2.34))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 3e-3})
    first = epoch()
    for _ in range(3):
        last = epoch()
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first * 0.5, (first, last)


def test_trained_checkpoint_serves_directly():
    """The four graphs share one parameter set: a BucketingModule
    training checkpoint drops straight into a GenerativeSession (no
    rename, no re-export) and the served generation follows the
    training-learned structure."""
    from mxnet_tpu import rnn

    rng = np.random.RandomState(0)
    V, B = 20, 16
    sents = []
    for _ in range(160):
        n = rng.randint(4, 12)
        s = [int(rng.randint(2, V))]
        for _ in range(n - 1):
            s.append((s[-1] * 3 + 1) % (V - 2) + 2)
        sents.append(s)
    it = rnn.BucketSentenceIter(sents, B, buckets=[8, 12], invalid_label=0)
    lm = TransformerLM(vocab=V, num_layers=1, num_heads=2, d_model=32,
                       max_len=16)
    mod = mx.mod.BucketingModule(
        sym_gen=lm.sym_gen(invalid_label=0),
        default_bucket_key=it.default_bucket_key, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2.34))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 3e-3})
    for _ in range(4):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    arg, aux = mod.get_params()
    params = dict(arg)
    params.update(aux)

    gs = GenerativeSession("lm", lm, params, max_sessions=1,
                           max_len=lm.max_len, seq_buckets=[4])
    start = 5
    (r,) = _drive(gs, [GenerateRequest("lm", [start], 60.0, 6)])
    # the trained rule: next = (prev * 3 + 1) % (V - 2) + 2
    want, prev = [], start
    for _ in range(6):
        prev = (prev * 3 + 1) % (V - 2) + 2
        want.append(prev)
    assert r.tokens.tolist() == want, (r.tokens.tolist(), want)


def test_attention_ops_match_numpy_oracle():
    """Direct numpy oracles for every op ops/attention.py registers
    (the test_operator.py registry-coverage contract): LayerNorm,
    _sdp_attention, _cached_attention, _kv_cache_write,
    _add_positional, _add_positional_at, _take_step."""
    rng = np.random.RandomState(7)
    n, h, t, dh = 2, 2, 5, 4
    d = h * dh

    # LayerNorm
    x = rng.randn(n, t, d).astype(np.float32)
    gamma = rng.randn(d).astype(np.float32)
    beta = rng.randn(d).astype(np.float32)
    got = mx.nd.LayerNorm(mx.nd.array(x), mx.nd.array(gamma),
                          mx.nd.array(beta), eps=1e-5).asnumpy()
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    want = (x - mu) / np.sqrt(var + 1e-5) * gamma + beta
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)

    # _sdp_attention: causal softmax attention + per-head K/V reshapes
    q = rng.randn(n, t, d).astype(np.float32)
    k = rng.randn(n, t, d).astype(np.float32)
    v = rng.randn(n, t, d).astype(np.float32)
    ctx, kh, vh = mx.nd._sdp_attention(mx.nd.array(q), mx.nd.array(k),
                                       mx.nd.array(v), num_heads=h,
                                       causal=True)
    qh = q.reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    kh_ref = k.reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    vh_ref = v.reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    scores = np.einsum("nhqd,nhkd->nhqk", qh, kh_ref) / np.sqrt(dh)
    scores = np.where(np.tril(np.ones((t, t), bool))[None, None],
                      scores, -1e30)
    ctx_ref = np.einsum("nhqk,nhkd->nhqd", _np_softmax(scores), vh_ref)
    ctx_ref = ctx_ref.transpose(0, 2, 1, 3).reshape(n, t, d)
    assert np.allclose(ctx.asnumpy(), ctx_ref, rtol=1e-4, atol=1e-5)
    assert np.allclose(kh.asnumpy(), kh_ref) and np.allclose(vh.asnumpy(),
                                                             vh_ref)

    # _kv_cache_write + _cached_attention through their contract (write a
    # prompt, step, read back what the step attends to): the ring's
    # stored shape is the model's to choose, so nothing here indexes it
    max_len = 8
    _ring_case(h, dh, max_len, slots=[1, 2], lens=[3, 5], padded=0, seed=7)
    q1 = rng.randn(2, 1, d).astype(np.float32)

    # _add_positional / _add_positional_at
    pos = rng.randn(max_len, d).astype(np.float32)
    got = mx.nd._add_positional(mx.nd.array(x), mx.nd.array(pos)).asnumpy()
    assert np.allclose(got, x + pos[None, :t])
    idx = np.array([2, 6], np.float32)
    got = mx.nd._add_positional_at(mx.nd.array(q1), mx.nd.array(pos),
                                   mx.nd.array(idx)).asnumpy()
    assert np.allclose(got, q1 + pos[idx.astype(int)][:, None, :])

    # _take_step: per-row gather of one timestep
    tk = np.array([0, 3], np.float32)
    got = mx.nd._take_step(mx.nd.array(x), mx.nd.array(tk)).asnumpy()
    assert np.allclose(got, x[np.arange(n), tk.astype(int)])


def test_token_ops_match_numpy_oracle():
    """`_greedy_token` is `numpy.argmax` a row — the first index on a
    tie — written at ``last_token[slot]``; `_token_feed` takes the
    host's token where it is not negative and the slot's last one where
    it is."""
    rng = np.random.RandomState(3)
    logits = rng.randn(4, 11).astype(np.float32)
    logits[1, [2, 6]] = logits[1].max() + 1.0    # a tie: index 2 wins
    logits[3, 10] = logits[3].max() + 1.0        # the last column
    last = np.asarray([50, 51, 52, 53, 54, 55], np.float32)
    slot = np.asarray([4, 0, 2, 5], np.float32)
    token, wrote = mx.nd._greedy_token(mx.nd.array(logits),
                                       mx.nd.array(last), mx.nd.array(slot))
    want = np.argmax(logits, axis=1)
    assert want[1] == 2 and want[3] == 10
    assert token.asnumpy().tolist() == want.tolist()
    assert token.asnumpy().dtype == np.float32
    assert wrote.asnumpy().tolist() == [want[1], 51, want[2], 53, want[0],
                                        want[3]]
    # padded rows all write the scratch slot: the last row's token stays
    token, wrote = mx.nd._greedy_token(
        mx.nd.array(logits), mx.nd.array(last),
        mx.nd.array(np.asarray([1, 5, 5, 5], np.float32)))
    assert wrote.asnumpy().tolist() == [50, want[0], 52, 53, 54, want[3]]

    data = np.asarray([[7], [-1], [0], [-1]], np.float32)
    fed = mx.nd._token_feed(mx.nd.array(data), mx.nd.array(last),
                            mx.nd.array(slot))
    assert fed.asnumpy().tolist() == [[7], [50], [0], [55]]


def _np_softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _np_attention(q, ks, vs, scale=None):
    """Plain attention of one query ``(H, d_head)`` over a session's
    whole sequence ``ks``/``vs (T, H_kv, d_head)`` — the oracle the ring
    ops are held to.  Fewer K/V heads than query heads are REPEATED, each
    for its group of consecutive query heads; `scale` replaces
    ``1 / sqrt(d_head)``."""
    h, dh = q.shape
    ks, vs = (np.repeat(x, h // x.shape[1], axis=1) for x in (ks, vs))
    sc = np.einsum("hd,thd->ht", q, ks) * (
        1 / np.sqrt(dh) if scale is None else scale)
    return np.einsum("ht,thd->hd", _np_softmax(sc), vs)


@contextlib.contextmanager
def _tpu_kernel_interpreted(block_bytes):
    """Inside, `_cached_attention` takes the branch a lowering for the
    TPU keeps — the Pallas kernel — run by Pallas's interpreter, with
    blocks of `block_bytes` (a ring of test size would fit one).  Yields
    the list of kernel calls traced."""
    from mxnet_tpu.ops import attention

    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    attention._decode_attention.clear_cache()
    with mock.patch.object(attention.lax, "platform_dependent", take_tpu), \
            mock.patch.object(attention, "_BLOCK_BYTES", block_bytes), \
            mock.patch.object(attention, "_INTERPRET", True):
        yield calls


def _ring_case(h, dh, max_len, slots, lens, padded, seed, steps=2,
               kv_heads=None, scale=None, kernel_block=None,
               kernel_heads=None):
    """Drive `_kv_cache_write` + `_cached_attention` the way a serving
    session does and hold every step's context to `_np_attention` over
    the session's full sequence.

    Each session `i` owns ring slot ``slots[i]`` and has ``lens[i]``
    tokens cached: a prompt's K/V block (padded to a bucket with garbage
    behind its true length, as a padded prefill leaves it) goes in
    through `_kv_cache_write`; then `steps` decode steps run on the
    RETURNED rings, `padded` extra rows pointing at the scratch slot with
    length 0 beside the live ones.  A later step reads what an earlier
    one wrote, so a row that lands anywhere but ``(slot, :, length)``
    shows.  The rings are only ever built from ``cache_spec`` and
    handed from op to op: their axis order is the model's own.

    With `kernel_block` every step runs twice on the same rings: through
    the TPU kernel in interpret mode, at that many positions a block,
    and through the ``jax.numpy`` body — the kernel is held to numpy AND
    to the body (same rings exactly, contexts to float32 rounding), and
    its rings are the ones the next step gets.  `kernel_heads` K/V heads
    are all a block may hold (default: every head), so the kernel's grid
    walks ``kv / kernel_heads`` head groups."""
    rng = np.random.RandomState(seed)
    d = h * dh
    kv = h if kv_heads is None else kv_heads
    lm = TransformerLM(vocab=8, num_layers=1, num_heads=h, d_model=d,
                       max_len=max_len, num_kv_heads=kv_heads)
    options = {} if kv_heads is None else dict(num_kv_heads=kv, scale=scale)
    n_slots = max(slots) + 1
    scratch = n_slots                      # serving/decode.py's +1 slot
    shape = lm.cache_spec(n_slots + 1)["k_cache_0"].shape
    kc = mx.nd.array(rng.randn(*shape).astype(np.float32))
    vc = mx.nd.array(rng.randn(*shape).astype(np.float32))
    hist_k, hist_v = [], []
    for s, n in zip(slots, lens):
        t = min(max_len, n + 2)            # a bucket longer than the prompt
        kb = rng.randn(1, kv, t, dh).astype(np.float32)
        vb = rng.randn(1, kv, t, dh).astype(np.float32)
        if n:
            kc, vc = mx.nd._kv_cache_write(
                kc, vc, mx.nd.array(kb), mx.nd.array(vb),
                mx.nd.array(np.array([s], np.float32)))
        hist_k.append(list(kb[0, :, :n].transpose(1, 0, 2)))
        hist_v.append(list(vb[0, :, :n].transpose(1, 0, 2)))
    live = len(slots)
    b = live + padded
    slot = np.array(list(slots) + [scratch] * padded, np.float32)
    for _ in range(steps):
        length = np.array([len(k) for k in hist_k] + [0] * padded,
                          np.float32)
        q, k, v = (rng.randn(b, 1, width).astype(np.float32)
                   for width in (d, kv * dh, kv * dh))
        step = lambda: mx.nd._cached_attention(
            mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), kc, vc,
            mx.nd.array(slot), mx.nd.array(length), num_heads=h, **options)
        if kernel_block:
            body = [x.asnumpy() for x in step()]
            with _tpu_kernel_interpreted(
                    (kernel_heads or kv) * dh * kernel_block * 4) as ran:
                ctx, kc, vc = step()
                got = ctx.asnumpy()       # traced and run inside the patch
                kc, vc = mx.nd.array(kc.asnumpy()), mx.nd.array(vc.asnumpy())
            assert len(ran) == 1
            # padded rows all write the scratch slot: any of them may win
            assert np.array_equal(kc.asnumpy()[:scratch], body[1][:scratch])
            assert np.array_equal(vc.asnumpy()[:scratch], body[2][:scratch])
            assert np.allclose(got[:live], body[0][:live], rtol=2e-6,
                               atol=2e-6)
        else:
            ctx, kc, vc = step()
        assert kc.shape == shape and vc.shape == shape
        got = ctx.asnumpy()
        assert got.shape == (b, 1, d) and np.isfinite(got).all()
        for i in range(live):
            hist_k[i].append(k[i, 0].reshape(kv, dh))
            hist_v[i].append(v[i, 0].reshape(kv, dh))
            want = _np_attention(q[i, 0].reshape(h, dh), np.stack(hist_k[i]),
                                 np.stack(hist_v[i]), scale)
            assert np.allclose(got[i, 0], want.reshape(d), rtol=1e-4,
                               atol=1e-5), (i, len(hist_k[i]))


_MAX_LEN = 12
RING_CASES = {
    # sessions two slots apart, neither at slot 0
    "nonadjacent_slots": dict(slots=[1, 4], lens=[3, 5], padded=0),
    # one live row in a bucket of four: three padded rows share the scratch
    # slot (duplicate writes there, length 0) and must not leak
    "padded_rows_share_scratch": dict(slots=[2], lens=[4], padded=3),
    # an empty session (its first step attends only to itself) beside one
    # whose second step writes the ring's last position, max_len - 1
    "length_zero_and_ring_end": dict(slots=[0, 3],
                                     lens=[0, _MAX_LEN - 2], padded=0),
    # a full bucket: every slot live, in an order that is not the slots'
    "full_bucket_shuffled": dict(slots=[2, 0, 3, 1], lens=[1, 7, 2, 5],
                                 padded=0),
    # grouped-query heads (a ring of 2 K/V heads read by 6 query heads)
    # and a stated scale in place of 1/sqrt(d_head), beside padded rows
    "grouped_query_heads": dict(h=6, kv_heads=2, scale=0.03, slots=[3, 1],
                                lens=[0, 6], padded=2),
}


# The TPU kernel (ops/kv_ring_kernel.py) in interpret mode: rings of 256
# positions read in blocks of 128, so `lens` straddle the block edge
_KERNEL = dict(max_len=256, kernel_block=128)
RING_CASES.update({
    # B = 1: the step writes the first block's last position (127), the
    # next one the second block's first (128)
    "kernel_one_row": dict(_KERNEL, slots=[2], lens=[127], padded=0),
    # B = 8, every slot live and shuffled: empty, either side of the
    # block edge, and a ring whose second step writes max_len - 1
    "kernel_full_bucket_block_edges": dict(
        _KERNEL, slots=[3, 0, 6, 1, 7, 4, 2, 5],
        lens=[0, 126, 127, 128, 129, 254, 60, 200], padded=0),
    # non-adjacent slots beside padded rows that share the scratch slot
    "kernel_padded_rows_share_scratch": dict(
        _KERNEL, slots=[2, 5], lens=[0, 130], padded=2),
    # four query heads a ring head, a stated scale, padded rows
    "kernel_grouped_query_heads": dict(
        _KERNEL, h=8, kv_heads=2, scale=0.03, slots=[3, 1], lens=[127, 5],
        padded=2),
    # a ring too wide for one block of all its heads (at the published
    # sizes: 30 heads x 128 of 2,304 positions, 15 a block): six heads
    # walked two at a time, groups of one, rows on either side of the edge
    "kernel_head_groups": dict(
        _KERNEL, h=6, kernel_heads=2, slots=[1, 4, 0], lens=[127, 3, 200],
        padded=1),
})


@pytest.mark.parametrize("d_head", [64, 128])
@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_ring_ops_match_full_sequence_attention(case, d_head):
    """`_cached_attention` + `_kv_cache_write` against plain numpy
    attention over each session's full sequence, two steps in a row on
    the returned rings, at both head widths the benchmark's decoders
    have (64: OPT, Granite's attention layers; 128: OLMoE).  The
    ``kernel_`` cases run the TPU's kernel (interpreted) beside the
    ``jax.numpy`` body."""
    _ring_case(**dict(dict(h=2, max_len=_MAX_LEN), **RING_CASES[case]),
               dh=d_head, seed=11)


def _walk_eqns(jaxpr, platform="cpu"):
    """Every equation of `jaxpr` and of the jaxprs its equations carry
    (pjit bodies, custom_jvp calls); an equation that carries one is its
    wrapper, not work of its own, and is not yielded.  Of a
    ``lax.platform_dependent`` choice only the branch that a lowering
    for `platform` keeps is walked."""
    for eqn in jaxpr.eqns:
        inner = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                 if hasattr(getattr(v, "jaxpr", v), "eqns")]
        if eqn.primitive.name == "pallas_call":
            inner = []                    # a kernel is work of its own
        chosen = eqn.params.get("branches_platforms")
        if chosen:
            index = [i for i, names in enumerate(chosen)
                     if names is None or platform in names][0]
            inner = [eqn.params["branches"][index].jaxpr]
        if inner:
            for j in inner:
                yield from _walk_eqns(j, platform)
        else:
            yield eqn


@pytest.mark.parametrize("bucket", [2, 4])
def test_decode_program_touches_a_ring_only_by_row_updates(bucket):
    """The invariant of PR 26, where the CPU can see it: in the jaxpr of
    a small LM's decode program, as a lowering for the CPU keeps it, the
    ONLY equations that produce an array of ``B x H x max_len x d_head``
    elements or more are the rings' row updates — B
    `dynamic_update_slice`s a ring — so there is no gathered page batch,
    no scatter and no ring-sized temporary; and what reads a ring is a
    `dynamic_slice` of ONE page (which XLA fuses into the reduction that
    consumes it), never more.  As a lowering for the TPU keeps it (PR
    32), the rings are touched by ONE kernel call a layer and by nothing
    else.  chip_smoke.py holds the compiled program to the same on the
    chip."""
    import jax

    # a ring long enough that one page outweighs every weight matrix,
    # of a shape the TPU's kernel tiles (two heads of 64)
    max_len = 1024
    lm, params = _lm_and_params(num_layers=2, num_heads=2, d_model=128,
                                max_len=max_len)
    gs = GenerativeSession("lm", lm, params, max_sessions=4,
                           max_len=max_len, seq_buckets=[8])
    try:
        exe, fn = gs._program(gs._decode_pred, bucket, 1, False)
        other, aux = exe.serve_args(gs._input_names)
        ins = (np.zeros((bucket, 1), np.float32),
               np.zeros((bucket,), np.float32),
               np.zeros((bucket,), np.float32)) + tuple(gs._state)
        jaxpr = jax.make_jaxpr(fn._jit)(ins, other, aux, np.uint32(0))
    finally:
        gs.close()
    ring = tuple(lm.cache_spec(5, max_len)["k_cache_0"].shape)
    page = int(np.prod(ring[1:]))
    updates, token_writes, big, page_makers = 0, 0, [], set()
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for out in eqn.outvars:
            size = int(np.prod(out.aval.shape)) if out.aval.shape else 1
            if eqn.primitive.name == "dynamic_update_slice":
                if tuple(out.aval.shape) == ring:
                    updates += 1
                else:  # the sampled token, one a row, into `last_token`
                    assert tuple(out.aval.shape) == (5,)
                    token_writes += 1
            elif size >= bucket * page:
                big.append((eqn.primitive.name, tuple(out.aval.shape)))
            elif size >= page:
                page_makers.add(eqn.primitive.name)
    assert big == [], big
    # B rows x (K ring + V ring) x layers
    assert updates == bucket * 2 * lm.num_layers
    assert token_writes == bucket
    assert page_makers <= {"dynamic_slice", "squeeze", "reshape"}, \
        page_makers
    on_tpu = [eqn for eqn in _walk_eqns(jaxpr.jaxpr, "tpu")
              if any(tuple(out.aval.shape) == ring for out in eqn.outvars)]
    # the kernel, lowered once a shape and called exported (ops/exported.py)
    assert [eqn.primitive.name for eqn in on_tpu] == \
        ["call_exported"] * lm.num_layers


@pytest.mark.parametrize("ring,platform,block,heads", [
    ((9, 32, 64, 768), "tpu", 128, 32),       # OPT: all heads, as before
    ((9, 16, 128, 768), "tpu", 128, 16),      # OLMoE
    ((9, 8, 64, 2304), "tpu", 384, 8),        # Granite
    ((9, 30, 128, 2304), "tpu", 128, 15),     # Olmo-Hybrid: 1.875 MiB of heads
    ((9, 64, 128, 768), "tpu", 128, 16),
    ((3, 2, 64, 256), "tpu", 256, 2),
    ((9, 32, 64, 768), "cpu", None, 32),
    ((5, 2, 8, 48), "tpu", None, None),       # no 128 divides; 16 lines
    ((5, 2, 24, 256), "tpu", None, None),     # 128 % d_head
    ((5, 1, 64, 256), "tpu", None, None),     # half a tile
    ((9, 3, 64, 256), "tpu", None, None),     # no group of whole tiles
])
def test_decode_block_is_read_off_the_rings_shape_and_the_platform(
        ring, platform, block, heads):
    """`ops.attention.decode_block` / `decode_heads`: the largest multiple
    of 128 positions that divides the ring and keeps one block within
    1 MiB of float32 — a block of ALL K/V heads wherever 128 positions of
    them fit (the three rings before Olmo-Hybrid's pick what they picked),
    else of the most whole heads that divide them and fill 128-line
    tiles — where the TPU's kernel runs; None where the ``jax.numpy``
    body reads whole pages.  Nothing but the ring's bytes is asked."""
    from mxnet_tpu.ops.attention import decode_block, decode_heads

    assert decode_block(ring, platform) == block
    assert decode_heads(ring) == heads


@pytest.mark.parametrize("two_programs", [True, False])
@pytest.mark.parametrize("block", [None, 128])
def test_page_and_skipped_position_counters(block, two_programs,
                                            monkeypatch):
    """Per decode step `kv.page_positions` grows by ``max_len`` a packed
    row and `kv.skipped_positions` by what the dispatched program's
    attention does not read of those pages: nothing where it reads whole
    pages (the CPU's program: `block` None), everything beyond the block
    that holds `length` where it reads by blocks.  A row that rides a
    mixed step counts like any other: the same rows at the same lengths,
    in one dispatch more."""
    from mxnet_tpu.ops import attention

    max_len = 256
    if block:  # what a session on a TPU is told; the counters are host side
        monkeypatch.setattr(attention, "decode_block",
                            lambda shape, platform, itemsize=4: block)
    lm, params = _lm_and_params(max_len=max_len, two_programs=two_programs)
    telemetry.set_enabled(True)
    names = ("kv.page_positions", "kv.skipped_positions",
             "kv.used_positions", "serving.decode.dispatches")
    before = {n: telemetry.counter_value(n) for n in names}
    gs = GenerativeSession("lm", lm, params, max_sessions=2, max_len=max_len,
                           max_decode_tokens=16, seq_buckets=[8, 128])
    try:
        assert set(gs._ring_blocks.tolist()) == {block or max_len}
        # lengths 5..9 stay in the first block; 126..130 cross into the
        # second at the third of five steps
        reqs = [GenerateRequest("lm", [1 + i % 20 for i in range(n)], 60.0, 6)
                for n in (5, 126)]
        _drive(gs, reqs)
    finally:
        gs.close()
    moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    lengths = [5 + i for i in range(5)] + [126 + i for i in range(5)]
    # the second prompt's mixed step carries the first session's row
    assert moved["serving.decode.dispatches"] == 5 + (not two_programs)
    assert moved["kv.used_positions"] == sum(lengths)
    assert moved["kv.page_positions"] == len(lengths) * max_len
    read = sum((n // block + 1) * block for n in lengths) if block else \
        len(lengths) * max_len
    assert moved["kv.skipped_positions"] == len(lengths) * max_len - read
    assert moved["kv.skipped_positions"] == (
        (5 * 128 + 2 * 128) if block else 0)


# ----------------------------------------------------------------------
# the graphs every configuration binds, and a kind's own sizes (PR 56)
# ----------------------------------------------------------------------

GRAPHS = ("training_symbol", "score_symbol", "prefill_symbol",
          "decode_symbol", "mixed_symbol")
# configuration: (family, the cell's slots, sha1 of `tojson()` of GRAPHS as
# `benchmarks.families.<family>.model(config)` built them from the
# benchmark's own configuration file AT THE PARENT COMMIT (bc80f38, before
# the kinds owned their sizes) — graph construction only, each under a
# NameManager of its own; None: the configuration has no mixed form
FAMILY_GRAPHS = {
    "opt-1.3b": ("opt", 8,
        "6ad731022342e4b8f5583f0debb0ce00d757abfe",
        "1036bf47d1f9ca438177e4807a7120749cea233b",
        "2e76b7f6ab2fc05337340930cefa08923407e09e",
        "393430e83d62cac24c30a4e0aed47f7f8e50650f",
        "acefd4f82b59f145fa73b295d1f03f3da94f29ef"),
    "olmoe-1b-7b": ("olmoe", 8,
        "3875e31773ed0a8af8e1db9c5cc9d6e24a97bbbd",
        "d5785914ed4170a138deea55fdc36ae073630e58",
        "bd4be60fb1b6685e0d4038f833136f8217e14663",
        "c7557c24b3b581b54e765b9d0c084c5f4265ffa0",
        "34d648acb1941b12c75092fad146590b7c975862"),
    "granite-4.0-h-micro": ("granite_hybrid", 8,
        "087821df4244e18d542902f0c42eb9eaa2948bd2",
        "679394e4132b8d5fd82797203f9b32a47ad03595",
        "a2bf615c635a3f7f1ca0c15895fd1c779776240e",
        "8bccda39c3fa55d5cf2f8ce87af40c84d38c199d",
        None),
    "olmo-hybrid-7b": ("olmo_hybrid", 8,
        "7109e9032e3c97cb9db0983865b532c7d4b965d5",
        "84e606c0357771a7689eeb07c8b75d1a1f209d4d",
        "9ca47d0cadaa97c8a310cc78fea7fc68c70f9d46",
        "4e8abcd9794e4123f9c1bc32d8487b32035f75a1",
        "25f3d04c39004c98a53131a6bf197e7d3b0b77ff"),
    "trinity-mini": ("afmoe", 8,
        "d1b463c67efe0cddb0b3b11928906eb24db70339",
        "6e327883e6a050a6467c7bf2c6af51df3720ec59",
        "e0e80f515d3db50b41fbb1d1fbfc55ea23e66a72",
        "55a5295c67dc63d92505d16e88b7c36646d8cc0e",
        "1aaed8cff56aab3481481ef07fb097635d3e3368"),
    "qwen3-next-80b-a3b": ("qwen3_next", 16,
        "3208fa81c88a908d7d4cb284460b770d72e2dcd2",
        "79d573c9b6865da27dc3487da4f200b8f2651c2f",
        "85c6ef01e68bebb1adc657ffef0109752a9652f2",
        "e83b7b1f379695bf0678614014d7103d144e3b08",
        "1ebc119fc90648f427e25b6456e851a6e83f1f96"),
    "mistral-small-4-119b": ("mistral4", 16,
        "40ff36540e20e755a4381f74ccc56d86566e83d8",
        "582b5ca27eb8b87d0dcc4bccc2875e6b72dd9ae8",
        "c52917aa45c1af061dff3992bfdafc9222501a31",
        "9ceeb654732c44badf180821e48c585704b00db8",
        None),
    "dots3-note-prev": ("dots3", 4,
        "3522b62a3bcfd5130d44e9eea25797aa0e7a4ebc",
        "43e9e7dbf37bdf274a8ce93a5d65fbd8424ea62e",
        "8676552ed85408b2579560d845d5c8adbdeab41d",
        "1bee16480cdc89e682cf08528bd3f22f327b7294",
        None),
    "glm-5": ("glm5", 4,
        "314be826d7169938cfb8825939bb8ab7b3302c78",
        "60b23443d211605f7338b6dfea25b25cd699df0a",
        "924b713673d73f3badfdf2857f301922601e8ce7",
        "cf7605ca41661e0c3a7664c8e141df0055e9e441",
        None),
    "granite-4.0-h-small": ("granite_moe_hybrid", 8,
        "baa90f0a116d9adc20f7a1c9ebafe2f39d525d06",
        "5455591ed5a4038604e68318c31bc3b2c6f3db80",
        "58198bd18192884e74e96cce8ed7b9edb8191500",
        "d1d3a3bb025bab538b2601b81a9bcfd9d263689f",
        None),
}
# three layers of each — six of Granite's, so that both of its kinds are in
# — at the published widths, hashed at older parents yet (OPT's and OLMoE's
# at 2109c79, before the layer kinds; Granite's at 2ac94fd, before
# `block_norm` and a third kind): GRAPHS' first four
OLDER_GRAPHS = {
    "opt": (dict(vocab=50272, num_layers=3, num_heads=32, d_model=2048,
                 d_ff=8192, max_len=2048),
            "c5ec39dad62315ec1bebce350b266fd623536162",
            "1977b83bd3110ebd66b30ec94cf150b7f2303b14",
            "48532b55682f2ad6f69d722de119d7756fa8ea0c",
            "124e97c9ed89d78016b93f4d8b74cc7fd6319fc2"),
    "olmoe": (dict(vocab=50304, num_layers=3, num_heads=16, d_model=2048,
                   d_ff=1024, max_len=4096, norm="rms", norm_eps=1e-5,
                   positions="rotary", rope_theta=10000.0, qk_norm=True,
                   num_experts=64, experts_per_token=8, bias=False,
                   tied_head=False),
              "833cffed802b0f12737ec4fef09cb890e2c6483c",
              "321af145a4a4a583067ccca139885bfd3d167f67",
              "4d1b2ec2e4c78401528d2adddc81654106b81e2e",
              "529499e280108dda9508fcadc203aed86013ca5c"),
    "granite": (dict(vocab=100352, num_layers=6, num_heads=32, d_model=2048,
                     d_ff=8192, max_len=131072, norm="rms", norm_eps=1e-5,
                     positions="none", bias=False, tied_head=True,
                     layer_types=["mamba"] * 5 + ["attention"],
                     num_kv_heads=8, ffn="swiglu", embedding_multiplier=12,
                     residual_multiplier=0.22, attention_multiplier=0.015625,
                     logits_scaling=8, mamba_heads=64, mamba_head_dim=64,
                     mamba_state=128, mamba_groups=1, mamba_conv=4,
                     mamba_chunk=256),
                "2b28d9946c4695960b7074ebda9ca33a339f8313",
                "a0ec10075e3e19cc3b622dd27d27918653fc5d68",
                "c2c586ac196572182bd0cbcf039cabbbec139fe7",
                "e93f15ca90cefaf24c32446c38d3d1b603947dde"),
}
# what Qwen3-Next's options put on a node (PR 40), and the serving graphs
# that must go on carrying none of it
MARKS = ("num_key_heads", "rotary_dim", "shared_gate")
UNMARKED = [(name, graph)
            for name in ("olmo-hybrid-7b", "trinity-mini", "olmoe-1b-7b")
            for graph in GRAPHS[1:4]]


def _family_model(name):
    """The model of configuration `name` as its family builds it from the
    benchmark's own file."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return importlib.import_module(
        "benchmarks.families." + FAMILY_GRAPHS[name][0]).model(config)


def _graph_json(build, graph, *args):
    with mx.name.NameManager():  # auto-names count from 0, as in a new process
        built = getattr(build(), graph)(*args)
    return None if built is None else built.tojson()


def _sha1(js):
    return None if js is None else hashlib.sha1(js.encode()).hexdigest()


@pytest.mark.parametrize("family,graph", [
    pytest.param(name, graph, id="%s-%s" % (name, graph))
    for name in FAMILY_GRAPHS for graph in GRAPHS] + [
    pytest.param(name, graph, id="three-layer-%s-%s" % (name, graph))
    for name in OLDER_GRAPHS for graph in GRAPHS[:4]] + [
    pytest.param(name, graph, id="%s-%s-unmarked" % (name, graph))
    for name, graph in UNMARKED])
def test_every_family_builds_the_parents_graphs(family, graph, request):
    """Every graph every cell binds serialises to the parent's bytes: the
    same programs, the same compile-cache keys.  The three-layer cases
    hold OPT, OLMoE and Granite to parents older yet; the unmarked cases
    hold three configurations' serving graphs to carrying no attribute a
    later model's options brought, which that model's nodes do carry."""
    if family in OLDER_GRAPHS:
        args, *pins = OLDER_GRAPHS[family]
        js = _graph_json(lambda: TransformerLM(**args), graph)
    else:
        _, slots, *pins = FAMILY_GRAPHS[family]
        js = _graph_json(lambda: _family_model(family), graph,
                         *[slots] * (graph == "mixed_symbol"))
    assert _sha1(js) == pins[GRAPHS.index(graph)]
    if request.node.callspec.id.endswith("unmarked"):
        words = json.dumps(json.loads(js)).replace('"', " ").split()
        assert not set(MARKS) & set(words)
        mine = _family_model("qwen3-next-80b-a3b").decode_symbol().tojson()
        assert all(n in mine for n in MARKS)


# kind: (its flat prefix, sizes that build it, mappings over them that it
# refuses)
KIND_SIZES = {
    "mamba": ("mamba_", dict(heads=4, head_dim=4, state=8, groups=2,
                             chunk=8),
              [dict(heads=3), dict(groups=0), dict(conv=1)]),
    "linear_attention": ("linear_", dict(heads=4, key_dim=4, value_dim=8,
                                         key_heads=2, neg_eigval=False),
                         [dict(heads=3), dict(key_heads=-1), dict(conv=1)]),
    "latent_attention": ("latent_", dict(q_rank=8, kv_rank=8, nope_dim=4,
                                         rope_dim=4, value_dim=8),
                         [dict(rope_dim=3, value_dim=7), dict(value_dim=16),
                          # the model's heads and rotary base, no others
                          dict(num_heads=2), dict(rope_theta=1e4)]),
    "sparse_latent_attention": (None, dict(
        num_heads=2, q_rank=8, kv_rank=8, nope_dim=4, rope_dim=4,
        value_dim=6, index_heads=2, index_dim=8, index_topk=4,
        head_gate=True), [dict(rope_dim=3), dict(index_dim=2)]),
    "window_latent_attention": (None, dict(
        num_heads=2, q_rank=8, kv_rank=8, nope_dim=4, rope_dim=4,
        value_dim=6, window=5), [dict(rope_dim=3), dict(window=0)]),
}


@pytest.mark.parametrize("kind", sorted(KIND_SIZES))
def test_a_kind_owns_its_sizes(kind):
    """A kind's sizes are `kind_specs[kind]`, checked where the kind is
    built; the three older kinds' flat keywords are that mapping spelt
    another way, and nothing else is a keyword."""
    prefix, sizes, refused = KIND_SIZES[kind]
    base = dict(vocab=8, num_layers=1, num_heads=2, d_model=16, norm="rms",
                layer_types=[kind])

    def shas(**args):
        return [_sha1(_graph_json(lambda: TransformerLM(**base, **args), g))
                for g in GRAPHS[:4]]

    by_kind = shas(kind_specs={kind: sizes})
    assert None not in by_kind
    for fault in [{next(iter(sizes)): None}, {"bogus": 1}] + refused:
        with pytest.raises(ValueError, match=repr(kind)):
            TransformerLM(**base, kind_specs={kind: dict(sizes, **fault)})
    with pytest.raises(ValueError, match=repr(kind)):
        TransformerLM(**base)
    with pytest.raises(TypeError, match="bogus"):
        TransformerLM(**base, kind_specs={kind: sizes},
                      **{(prefix or "sparse_") + "bogus": 1})
    if prefix is None:
        return
    flat = {prefix + k: v for k, v in sizes.items()}
    assert shas(**flat) == by_kind
    # a kind is spelt one way: a size given both ways is refused
    name, value = next(iter(flat.items()))
    with pytest.raises(ValueError, match=name):
        TransformerLM(**base, kind_specs={kind: sizes},
                      **{name: value + 1})
    lm = TransformerLM(**base, **flat)
    assert lm.kind_specs[kind] == sizes
    assert not [a for a in vars(lm) if a.startswith(prefix)]
