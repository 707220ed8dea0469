"""A routed FFN under Mamba-2 mixers through `TransformerLM` and
`GenerativeSession`: Granite 4.0-H Small's block — Mamba-2 and
grouped-query NoPE attention mixers, and in every layer the ten (here:
four) largest router logits' experts beside one shared MLP, the 0.22
residual multiplier on their SUM — whole and as ONE CHIP'S SHARE, against
the plain reference of the benchmark
(benchmarks/reference/granite_moe_hybrid.py: float32 `jax.numpy` at
"highest", independent of `mxnet_tpu`).

Tiny widths (4 layers `[mamba, mamba, attention, mamba]`, hidden 64, 4
Mamba heads x 16, 16 states, chunk 8, 4 query / 2 K/V heads, 12 experts
of width 24 of which a share holds 3, 4 a token, a shared MLP of 48),
both sides float32 on the CPU: errors are float32 rounding (measured
1e-7 of the largest logit); the bound 1e-4 is far above that and a
fortieth of what one bfloat16 pass leaves.  The file costs about 60 s.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.families import granite_hybrid as micro_family  # noqa: E402
from benchmarks.families import granite_moe_hybrid as family  # noqa: E402
from benchmarks.reference import granite_moe_hybrid as reference  # noqa: E402

WHOLE = {"vocab_size": 40, "hidden_size": 64, "num_hidden_layers": 4,
         "layer_types": ["mamba", "mamba", "attention", "mamba"],
         "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
         "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 24, "shared_intermediate_size": 48,
         "num_local_experts": 12, "router_experts": 12,
         "held_experts": None, "num_experts_per_tok": 4,
         "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
         "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
         "logits_scaling": 16, "tie_word_embeddings": True,
         "max_position_embeddings": 64, "param_dtype": "float32",
         "state_dtype": "float32"}
HELD = 3
SHARES = [dict(WHOLE, num_local_experts=HELD, held_experts=[first, HELD])
          for first in range(0, WHOLE["router_experts"], HELD)]
SHARE = SHARES[1]
# the micro preset: the same mixers over a dense MLP (tests/test_granite_hybrid.py)
MICRO = {k: v for k, v in WHOLE.items()
         if k not in ("intermediate_size", "num_local_experts",
                      "router_experts", "held_experts",
                      "num_experts_per_tok")}
MICRO = dict(MICRO, shared_intermediate_size=96, logits_scaling=8)
RTOL = 1e-4  # of the largest |logit|; see the module docstring
EXPERT_KEYS = ("gate_weight", "up_weight", "down_weight")


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
    # conv's bias and the gains; x5 makes every part of the block matter
    # (the router is drawn by its logits' deviation, ~1.3 at any width)
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
    """Layer 0's routed FFN node AS THE MODEL BUILDS IT (`_RoutedFFN.apply`
    -> `mx.sym.MoE` with the model's own attributes) on normed input `u
    (T, d)`: ``Routed(u) + Shared(u)`` of the experts `config` holds."""
    lm = family.model(config)
    ffn = lm._ffns[0]
    names = ffn.params(0)
    node = ffn.apply(mx.sym.Variable("u"), names, 0, None)
    held = {"l0_" + k: mx.nd.array(np.asarray(params["l0_" + k]))
            for k in names}
    pred = mx.Predictor(node, held, {"u": (1,) + u.shape})
    pred.forward(u=u[None])
    return pred.get_output(0)[0]


def _reference_ffn(config, params, u, shared=True):
    import jax

    first = 0 if config["held_experts"] is None else config["held_experts"][0]
    p = {k: params["l0_" + k] for k in reference.ROUTED[1:]}
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.expert_layer(
            u, p["router_weight"], p["gate_weight"], p["up_weight"],
            p["down_weight"],
            (p["shared_gate_weight"], p["shared_up_weight"],
             p["shared_down_weight"]) if shared else None,
            config["num_experts_per_tok"], first)[0])


def _normed_rows(t=24, seed=2):
    rng = np.random.RandomState(seed)
    u = rng.randn(t, WHOLE["hidden_size"]).astype(np.float32)
    return u / np.sqrt((u * u).mean(-1, keepdims=True))


# ----------------------------------------------------------------------
# (a) the model against the reference: scoring graph, then the cache
# ----------------------------------------------------------------------


@pytest.mark.parametrize("which", ["whole", "share"])
@pytest.mark.parametrize("length", [2, 8, 21])
def test_score_symbol_matches_the_reference(params, which, length):
    config = WHOLE if which == "whole" else SHARE
    mine = _share_of(params, config)
    tokens = np.random.RandomState(length).randint(0, 40, length)
    _close(_score(family.model(config), _hold(mine), tokens),
           reference.logits(mine, config, tokens))


@pytest.mark.parametrize("which", ["whole", "share"])
def test_prefill_then_decode_through_the_cache_matches_one_full_forward(
        params, which):
    """Prefill (padded buckets: 11 in 32, 5 in 8) then ten decode steps of
    two sessions, one step of each in turn, through the session's own
    programs and state: every call's logits are the reference's at that
    position of that session's sequence — the whole layer, and one chip's
    share of it."""
    config = WHOLE if which == "whole" else SHARE
    mine = _share_of(params, config)
    rng = np.random.RandomState(7)
    seqs = [rng.randint(0, 40, 21), rng.randint(0, 40, 15)]
    starts, slots, buckets = [11, 5], [2, 0], [32, 8]
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
    each its own three experts' terms for the tokens routed to them,
    through the model's own `mx.sym.MoE` node — with the shared MLP, which
    every chip computes alike, counted once, is what the uncut reference
    gives for the whole layer; and the reference's own shares add up the
    same way."""
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


# ----------------------------------------------------------------------
# (c) the gates: a softmax over the kept logits
# ----------------------------------------------------------------------


def test_the_gates_are_a_softmax_over_the_kept_logits(params):
    """The reference's weights are the softmax of the four largest LOGITS
    and 0 elsewhere (the published form); the model's node — a softmax
    over all twelve, the four largest, renormalised — gives the same
    layer, whole and as a share."""
    import jax

    u = _normed_rows()
    weights, margin = reference.route(u, params["l0_router_weight"], 4)
    logits = u @ np.asarray(params["l0_router_weight"])
    for row, w in zip(logits, np.asarray(weights)):
        kept = np.argsort(-row)[:4]
        assert set(np.flatnonzero(w)) == set(kept)
        np.testing.assert_allclose(
            w[kept], np.asarray(jax.nn.softmax(row[kept])), rtol=1e-5)
    assert np.asarray(margin).min() > 0
    for config in (WHOLE, SHARE):
        mine = _share_of(params, config)
        _close(_routed_ffn(config, mine, u), _reference_ffn(config, mine, u))


@pytest.mark.parametrize("fault", ["not_renormalised", "four_of_the_held",
                                   "uniform_gates"])
def test_a_program_with_other_gates_fails(params, fault):
    """A softmax over all twelve used as it is (the four sum to ~0.6), a
    router that sees the HELD experts alone and keeps four of them, and
    gates of 1/4 each do not give the reference's share: the scoring
    graph's logits move by far more than the bound."""
    from mxnet_tpu.models import TransformerLM

    config = dict(SHARE, num_local_experts=6, held_experts=[3, 6])
    mine = _share_of(params, config)
    tokens = np.random.RandomState(4).randint(0, 40, 21)
    want = reference.logits(mine, config, tokens)
    args = family.model_args(config)
    _close(_score(TransformerLM(**args), _hold(mine), tokens), want)
    if fault == "not_renormalised":
        args["route_norm"] = False
    elif fault == "four_of_the_held":
        args.update(num_experts=6, held_experts=None)
        mine = {k: v[:, 3:9] if k.endswith("router_weight") else v
                for k, v in mine.items()}
    else:
        mine = {k: 0.0 * v if k.endswith("router_weight") else v
                for k, v in mine.items()}
    got = _score(TransformerLM(**args), _hold(mine), tokens)
    assert _rel(got, want) > 30 * RTOL, fault


# ----------------------------------------------------------------------
# (d) the multipliers, and the sliced head
# ----------------------------------------------------------------------


@pytest.mark.parametrize("key,other", [("residual_multiplier", 1.0),
                                       ("logits_scaling", 8),
                                       ("embedding_multiplier", 1),
                                       ("attention_multiplier", 0.25)])
def test_a_model_with_another_multiplier_fails(params, key, other):
    """Each of the four multipliers is felt: the same weights under a
    model that states another value leave the reference's logits."""
    mine = _share_of(params, SHARE)
    tokens = np.random.RandomState(6).randint(0, 40, 21)
    want = reference.logits(mine, SHARE, tokens)
    got = _score(family.model(dict(SHARE, **{key: other})), _hold(mine),
                 tokens)
    assert _rel(got, want) > 30 * RTOL, key


def test_the_residual_multiplier_is_on_the_sum_of_routed_and_shared(params):
    """``h' = a + 0.22 (Routed(u) + Shared(u))``: with the multiplier on
    the routed sum alone, or on the shared MLP alone, the reference's
    layer is another — and the model's is the first."""
    import jax

    mine = _share_of(params, SHARE)
    x = np.random.RandomState(8).randn(24, 64).astype(np.float32)
    names = reference.ROUTED
    out, _ = reference.routed_block(
        x, *(mine["l0_" + n] for n in names), top_k=4, first=3, eps=1e-5,
        residual=0.22)
    with jax.default_matmul_precision("highest"):
        u = np.asarray(reference.base._rms(x, mine["l0_ln2_gamma"], 1e-5))
    both = _reference_ffn(SHARE, mine, u)
    routed = _reference_ffn(SHARE, mine, u, shared=False)
    _close(out, x + 0.22 * both, rtol=1e-6)
    _close(x + 0.22 * _routed_ffn(SHARE, mine, u), out)
    for wrong in (x + 0.22 * routed + (both - routed),
                  x + routed + 0.22 * (both - routed)):
        assert _rel(wrong, out) > 30 * RTOL


def test_a_sliced_vocabulary_is_a_smaller_one(params):
    """The model over rows 0-39 of a vocabulary of 80 gives, for ids of
    the slice, the first 40 of the whole vocabulary's logits: the tied
    head, `logits_scaling` and the final norm see the rows held here and
    nothing else."""
    import jax

    rng = np.random.RandomState(9)
    more = family.INIT_STD * 5.0 * rng.randn(40, 64).astype(np.float32)
    wide = dict(_share_of(params, SHARE))
    wide["embed_weight"] = np.concatenate(
        [np.asarray(wide["embed_weight"]), more])
    wide = {k: jax.numpy.asarray(v) for k, v in wide.items()}
    tokens = rng.randint(0, 40, 21)
    whole = np.asarray(reference.logits(wide, dict(SHARE, vocab_size=80),
                                        tokens))
    assert whole.shape == (21, 80)
    got = _score(family.model(SHARE), _hold(_share_of(params, SHARE)),
                 tokens)
    _close(got, whole[:, :40])


# ----------------------------------------------------------------------
# (e) the two counters of the Mamba-2 layers
# ----------------------------------------------------------------------


def _micro_params():
    import jax

    return micro_family.make_params(MICRO, 5, jax.devices("cpu")[0])


@pytest.mark.parametrize("which", ["small", "micro"])
def test_the_state_space_counters_grow_as_stated(params, which):
    """`ssm.scan_positions` grows by a prefill's BUCKET (the pad is
    scanned) times the Mamba layers, `ssm.state_bytes` by 2 x a step's
    real rows x a layer's window-and-state bytes, summed over the Mamba
    layers — for the routed model and for the dense one
    (granite-4.0-h-micro's block)."""
    if which == "small":
        config, lm_family = SHARE, family
        held = _hold(_share_of(params, SHARE))
    else:
        config, lm_family, held = MICRO, micro_family, _hold(_micro_params())
    telemetry.set_enabled(True)
    names = ("ssm.scan_positions", "ssm.state_bytes",
             "serving.decode.dispatches", "serving.decode.tokens",
             "gdn.scan_positions", "gdn.state_bytes")
    before = {n: telemetry.counter_value(n) for n in names}
    gs = GenerativeSession("lm", lm_family.model(config), held,
                           max_sessions=2, max_len=48, max_decode_tokens=16,
                           seq_buckets=[8, 32])
    try:
        _drive(gs, [GenerateRequest("lm", list(range(1, 1 + n)), 60.0, b)
                    for n, b in ((5, 3), (11, 5))])
        spec = gs._spec
    finally:
        gs.close()
    moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    layers = config["layer_types"].count("mamba")
    assert layers == 3
    assert moved["ssm.scan_positions"] == (8 + 32) * layers
    # one slot's window and state, over the Mamba layers (the session's
    # buffers have slots + 1 pages)
    page = sum(e.nbytes for e in spec.values() if e.kind == "state") // 3
    window, state = 3 * (64 + 2 * 16) * 4, 4 * 16 * 16 * 4
    assert page == layers * (window + state)
    # each token after a request's first came from one row of one step
    rows = moved["serving.decode.tokens"]
    assert rows == (3 - 1) + (5 - 1)
    assert moved["ssm.state_bytes"] == 2 * rows * page
    assert moved["gdn.scan_positions"] == moved["gdn.state_bytes"] == 0


def test_the_counters_are_declared_by_the_mixer_kind():
    lm = family.model(SHARE)
    page = sum(e.nbytes for e in lm.cache_spec(1).values()
               if e.kind == "state")
    assert lm.call_counters(positions=32)["ssm.scan_positions"] == 3 * 32
    step = lm.call_counters(rows=2, lengths=(5, 9), computed=2, pages=9,
                            max_len=48)
    assert step["ssm.state_bytes"] == 2 * 2 * page
    assert step["ssm.scan_positions"] == 0
    assert step["moe.routed_pairs"] == 4 * 2 * 4   # layers x rows x k


# ----------------------------------------------------------------------
# (f) two programs: an admission between steps
# ----------------------------------------------------------------------


def test_an_admission_between_steps_leaves_every_other_rows_state_bit_equal(
        params):
    """`_Mamba2` has no mixed form, so the routed model keeps the two
    programs (`mixed_symbol` None, `gs._mixed` false) and an admission is
    a prefill PROGRAM between two decode steps: it writes its own slot's
    windows, states and rings whole and leaves every other slot's bits —
    the scratch slot's too — as they were, and the rows that were live go
    on to the reference's logits."""
    mine = _share_of(params, SHARE)
    lm = family.model(SHARE)
    assert lm.mixed_symbol(3) is None
    rng = np.random.RandomState(11)
    seqs = [rng.randint(0, 40, 19), rng.randint(0, 40, 14)]
    starts, slots = [9, 4], [2, 0]
    want = [np.asarray(reference.logits(mine, SHARE, s)) for s in seqs]
    gs = _session(_hold(mine), SHARE)
    try:
        assert not gs._mixed

        def prefill(tokens, slot, bucket):
            exe, fn = gs._program(gs._prefill_pred, 1, bucket, True)
            data = np.zeros((1, bucket), np.float32)
            data[0, :len(tokens)] = tokens
            return gs._run(exe, fn, data, np.full((1,), slot, np.float32),
                           np.full((1,), len(tokens), np.float32))

        def step(at):
            exe, fn = gs._program(gs._decode_pred, 2, 1, False)
            got = gs._run(
                exe, fn,
                np.asarray([[s[n + at]] for s, n in zip(seqs, starts)],
                           np.float32),
                np.asarray(slots, np.float32),
                np.asarray([n + at for n in starts], np.float32))
            for row, (ref, n) in enumerate(zip(want, starts)):
                _close(got[row], ref[n + at])

        for seq, n, slot in zip(seqs, starts, slots):
            prefill(seq[:n], slot, 32 if n > 8 else 8)
        for at in range(4):
            step(at)
        before = [np.asarray(buf).copy() for buf in gs._state]
        prefill(rng.randint(0, 40, 13), 1, 32)       # the admission
        others = [0, 2, 3]                            # 3: the scratch slot
        changed = 0
        for name, was, buf in zip(gs._spec, before, gs._state):
            now = np.asarray(buf)
            assert np.array_equal(now[others], was[others]), name
            changed += not np.array_equal(now[1], was[1])
        assert changed == len(gs._spec)
        for at in range(4, 8):
            step(at)
    finally:
        gs.close()


# ----------------------------------------------------------------------
# the check of the benchmark, as the cell runs it
# ----------------------------------------------------------------------


@pytest.fixture
def cpu_limits(monkeypatch):
    """The check's limits for the tiny size on the CPU, where both sides
    are float32 and differ by rounding alone (the module's constants are
    the chip's, whose projections multiply at one bfloat16 pass): the
    logits' and the states' limits are this file's RTOL and a tenth of it,
    and only ties closer than a thousandth are skipped — of twelve experts
    and three held, the chip's 0.05 would skip nearly every row."""
    monkeypatch.setattr(family, "NEAR_TIE", 1e-3)
    monkeypatch.setattr(family, "LOGIT_RTOL", RTOL)
    monkeypatch.setattr(family, "LOGIT_RTOL_HIGH", RTOL)
    monkeypatch.setattr(family, "PREFILL_STATE_RTOL", RTOL / 10)
    monkeypatch.setattr(family, "DECODE_STATE_RTOL", RTOL / 10)


def _check(config, held, params, **session):
    gs = _session(held, config, max_len=64, seq_buckets=[8, 16, 32],
                  **session)
    try:
        return family.check_against_reference(config, gs, params, 3, 8)
    finally:
        gs.close()


def test_the_check_of_the_benchmark_passes_with_every_slot_live(params,
                                                               cpu_limits):
    """`check_against_reference` on a tiny ladder and four slots: the four
    sequences of `granite_hybrid.check_prompts`, prefilled alone, stepped
    together through the 4-row decode program; far inside all limits."""
    mine = _share_of(params, SHARE)
    ok, facts = _check(SHARE, _hold(mine), mine, max_sessions=4)
    assert ok, facts
    assert facts["prompts"] == [23, 2, 5, 9]
    assert facts["buckets"] == [32, 8, 8, 16]
    assert facts["rows_a_step"] == 4 and facts["steps"] == 41
    assert facts["compared"] + facts["skipped"] == 4 * 42
    assert facts["remaining_share"] > 0.5
    assert facts["logit_rel_err_high"] < RTOL / 30
    assert facts["prefill_state_rel_err"] < 1e-6
    assert facts["decode_state_rel_err"] < 1e-6
    assert facts["not_as_stated"] == [] and facts["router_rel_err"] < 1e-5
    assert len(facts["prefill_state"]) == len(facts["decode_state"]) == 4


def test_the_check_fills_more_slots_than_check_prompts_has(params, cpu_limits):
    mine = _share_of(params, SHARE)
    ok, facts = _check(SHARE, _hold(mine), mine, max_sessions=6)
    assert ok, facts
    assert facts["prompts"] == [23, 2, 5, 9, 5, 10]
    assert facts["buckets"] == [32, 8, 8, 16, 8, 16]


@pytest.mark.parametrize("fault", ["attention_scale", "gates",
                                   "bfloat16_weights", "bfloat16_state",
                                   "bfloat16_reference"])
def test_the_check_refuses(params, fault, monkeypatch, cpu_limits):
    """Wrong models and lower precisions than the configuration states do
    not pass: no attention scale (1 for 1/16) and gates that are not
    renormalised by the logits' limits; weights rounded once to bfloat16
    by what the tenant holds; a recurrence that rounds its state to
    bfloat16 at every call by layer 0's state; and the reference itself in
    bfloat16 in the program's place (the control of the chip's limits) by
    the logits'."""
    import jax.numpy as jnp
    from mxnet_tpu.models import TransformerLM

    mine = _share_of(params, SHARE)
    held, model, control = _hold(mine), family.model(SHARE), None
    if fault == "attention_scale":
        model = family.model(dict(SHARE, attention_multiplier=1.0))
    elif fault == "gates":
        model = TransformerLM(**dict(family.model_args(SHARE),
                                     route_norm=False))
    elif fault == "bfloat16_weights":
        held = {k: mx.nd.array(np.asarray(jnp.asarray(v).astype(
            jnp.bfloat16).astype(jnp.float32))) for k, v in mine.items()}
    elif fault == "bfloat16_reference":
        control = "bfloat16"
    gs = GenerativeSession("lm", model, held, max_sessions=4, max_len=64,
                           max_decode_tokens=16, seq_buckets=[8, 16, 32])
    if fault == "bfloat16_state":
        kinds = [e.kind for e in gs._spec.values()]
        run = gs._run

        def round_the_state_at_every_call(*args):
            out = run(*args)
            gs._state = [
                jnp.asarray(buf).astype(jnp.bfloat16).astype(jnp.float32)
                if kind == "state" else buf
                for buf, kind in zip(gs._state, kinds + [None])]
            return out

        monkeypatch.setattr(gs, "_run", round_the_state_at_every_call)
    try:
        ok, facts = family.check_against_reference(SHARE, gs, mine, 3, 8,
                                                   control=control)
    finally:
        gs.close()
    assert not ok, facts
    if fault in ("attention_scale", "gates", "bfloat16_reference"):
        assert facts["logit_rel_err"] > 10 * family.LOGIT_RTOL
        assert facts["decode_state_rel_err"] < 1e-6
    elif fault == "bfloat16_weights":
        assert "embed_weight" in facts["not_as_stated"]
    else:
        assert facts["not_as_stated"] == []
        assert (facts["prefill_state_rel_err"] > family.PREFILL_STATE_RTOL
                or facts["decode_state_rel_err"] > family.DECODE_STATE_RTOL)
