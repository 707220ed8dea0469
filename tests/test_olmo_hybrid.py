"""A linear-attention hybrid through `TransformerLM` and
`GenerativeSession`: Olmo-Hybrid's block — Gated DeltaNet (delta-rule)
mixers with one full NoPE attention mixer among them, QK-norm over the
whole projections, a dense SwiGLU MLP in every layer, each branch's
OUTPUT RMS-normed, untied head — against the plain reference of the
benchmark (benchmarks/reference/olmo_hybrid.py: float32 `jax.numpy` at
"highest", the delta rule a `lax.scan` over positions, independent of
`mxnet_tpu`).

Tiny widths (4 layers `[linear, attention, linear, linear]`, hidden 64,
4 delta-rule heads of 8 x 16, chunk 8, 4 attention heads x 16), both
sides float32 on the CPU: errors are float32 rounding (measured 6e-6 of
the largest logit); the bound 1e-4 is far above that and a fortieth of
what one bfloat16 pass leaves.  The file costs about 60 s.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import olmo_hybrid as family  # noqa: E402
from benchmarks.reference import olmo_hybrid as reference  # noqa: E402

CONFIG = {"vocab_size": 40, "hidden_size": 64, "intermediate_size": 96,
          "num_hidden_layers": 4,
          "layer_types": ["linear_attention", "full_attention",
                          "linear_attention", "linear_attention"],
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "linear_num_key_heads": 4, "linear_num_value_heads": 4,
          "linear_key_head_dim": 8, "linear_value_head_dim": 16,
          "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
          "linear_chunk_size": 8, "rms_norm_eps": 1e-6,
          "attention_bias": False, "tie_word_embeddings": False,
          "max_position_embeddings": 64, "param_dtype": "float32",
          "state_dtype": "float32"}
RTOL = 1e-4  # of the largest |logit|; see the module docstring
CHUNK = CONFIG["linear_chunk_size"]


def _params(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x5 makes every part of the block matter
    p = family.make_params(config, seed, jax.devices("cpu")[0])
    return {k: 5.0 * v if k.endswith("_weight") and "conv" not in k else v
            for k, v in p.items()}


@pytest.fixture(scope="module")
def params():
    return _params(CONFIG)


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


def _score(lm, held, tokens):
    t = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(held), {"data": (1, t)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(t, lm.vocab)


def _session(held, config=CONFIG, **kw):
    kw = dict(dict(max_sessions=3, max_len=48, max_decode_tokens=16,
                   seq_buckets=[8, 32]), **kw)
    return GenerativeSession("lm", family.model(config), held, **kw)


# ----------------------------------------------------------------------
# the delta-rule ops alone, against a position-by-position recurrence
# ----------------------------------------------------------------------

H, DK, DV, K = 4, 8, 16, 4
CONV_DIM, D_INNER = H * (2 * DK + DV), H * DV
ATTRS = dict(num_heads=H, key_dim=DK, value_dim=DV, conv_kernel=K,
             chunk_size=CHUNK, neg_eigval=True, eps=1e-6)


def _mixer_inputs(n, t, seed):
    """A fused projection whose `b` column spreads ``beta = 2 sigmoid(b)``
    over (0, 2), and heads whose decay a position runs from 0.99 (A 0.01)
    to 1e-7 (A 16): the state nearly kept and nearly forgotten."""
    rng = np.random.RandomState(seed)
    data = rng.randn(n, t, CONV_DIM + D_INNER + 2 * H).astype(np.float32)
    data[..., CONV_DIM + D_INNER:CONV_DIM + D_INNER + H] *= 3.0     # b
    dt = np.array([0.9, 1.0, 1.1, 1.0])
    small = [rng.uniform(-0.5, 0.5, (K, CONV_DIM)),       # conv_weight
             dt + np.log(-np.expm1(-dt)),                 # dt_bias
             np.log([0.01, 0.5, 4.0, 16.0]),              # A_log
             1 + 0.1 * rng.randn(DV)]                     # norm_gamma
    return data, [v.astype(np.float32) for v in small]


def _silu(x):
    return x / (1 + np.exp(-x))


def _np_mixer(data, small, conv=None, state=None, beta_scale=2.0):
    """The Gated DeltaNet mixer one position at a time, float64: returns
    (y, conv window, state ``(H, d_v, d_k)``) after the last position."""
    w, dt_bias, a_log, gamma = (v.astype(np.float64) for v in small)
    conv = np.zeros((K - 1, CONV_DIM)) if conv is None else conv.copy()
    state = np.zeros((H, DV, DK)) if state is None else state.copy()
    ys = []
    for row in data.astype(np.float64):
        raw, z, b, a = np.split(row, [CONV_DIM, CONV_DIM + D_INNER,
                                      CONV_DIM + D_INNER + H])
        window = np.concatenate([conv, raw[None]])
        conv = window[1:]
        qkv = _silu((window * w).sum(0))
        q, k = (x.reshape(H, DK) for x in np.split(qkv[:2 * H * DK], 2))
        v = qkv[2 * H * DK:].reshape(H, DV)
        q, k = (x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
                for x in (q, k))
        q = q / np.sqrt(DK)
        beta = beta_scale / (1 + np.exp(-b))
        alpha = np.exp(-np.exp(a_log) * np.log1p(np.exp(a + dt_bias)))
        state = alpha[:, None, None] * state
        read = (state * k[:, None, :]).sum(-1)
        state = state + (beta[:, None] * (v - read))[:, :, None] \
            * k[:, None, :]
        o = (state * q[:, None, :]).sum(-1)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * gamma
        ys.append((o * _silu(z.reshape(H, DV))).reshape(D_INNER))
    return np.stack(ys), conv, state


def _stored(state):
    """``(H, d_v, d_k)`` as the session stores it: ``(d_k, H * d_v)``."""
    return state.transpose(2, 0, 1).reshape(DK, H * DV)


def _nd(*arrays):
    return [mx.nd.array(np.asarray(a, np.float32)) for a in arrays]


@pytest.mark.parametrize("length", [1, 3, CHUNK - 1, CHUNK, CHUNK + 1,
                                    2 * CHUNK, 3 * CHUNK + 5])
def test_chunked_rule_matches_the_recurrence(length):
    """`_gdn_scan` (chunk 8: a unit-triangular solve a chunk) against the
    mixer run one position at a time, at lengths that end inside and on a
    chunk, with `beta` up to 2 and decays from nearly 1 to nearly 0."""
    data, small = _mixer_inputs(2, length, seed=length)
    got = mx.nd._gdn_scan(*_nd(data, *small), **ATTRS).asnumpy()
    for n in range(2):
        _close(got[n], _np_mixer(data[n], small)[0], rtol=2e-5)
    # without `neg_eigval` beta stops at 1: the op reads the attribute
    got = mx.nd._gdn_scan(*_nd(data, *small),
                          **dict(ATTRS, neg_eigval=False)).asnumpy()
    _close(got[0], _np_mixer(data[0], small, beta_scale=1.0)[0], rtol=2e-5)


@pytest.mark.parametrize("length", [1, 2, 3, CHUNK, 11, 2 * CHUNK + 3])
def test_padded_prefill_leaves_the_state_of_the_true_length(length):
    """`_gdn_prefill` in a bucket of 24 with `length` true positions: the
    outputs up to `length`, the conv window and the state are those of an
    unpadded run of the true length (1 and 2 are shorter than the window:
    zeros before the sequence), written at `slot` over whatever the slot
    held, and no other slot is touched."""
    bucket, slots, slot = 3 * CHUNK, 4, 2
    data, small = _mixer_inputs(1, bucket, seed=20 + length)
    rng = np.random.RandomState(length)
    conv0 = rng.randn(slots, K - 1, CONV_DIM).astype(np.float32)
    gdn0 = rng.randn(slots, DK, H * DV).astype(np.float32)
    y, conv, gdn = (o.asnumpy() for o in mx.nd._gdn_prefill(
        *_nd(data, *small, conv0, gdn0, [slot], [length]), **ATTRS))
    want_y, want_conv, want_state = _np_mixer(data[0, :length], small)
    _close(y[0, :length], want_y, rtol=2e-5)
    _close(conv[slot], want_conv, rtol=1e-6)
    _close(gdn[slot], _stored(want_state), rtol=2e-5)
    others = [i for i in range(slots) if i != slot]
    assert np.array_equal(conv[others], conv0[others])
    assert np.array_equal(gdn[others], gdn0[others])


def test_decode_step_advances_each_rows_slot_and_pads_dirty_only_scratch():
    """`_gdn_step` for two live rows at slots 3 and 0 and two padded rows
    at the scratch slot 4: each live row continues ITS slot's window and
    state by one position; slots 1 and 2 are bit-for-bit untouched; only
    the scratch slot takes the padded rows' garbage."""
    slots = 5
    data, small = _mixer_inputs(4, 1, seed=9)
    rng = np.random.RandomState(2)
    conv0 = rng.randn(slots, K - 1, CONV_DIM).astype(np.float32)
    states = rng.randn(slots, H, DV, DK)
    gdn0 = np.stack([_stored(s) for s in states]).astype(np.float32)
    slot = [3, 0, 4, 4]
    y, conv, gdn = (o.asnumpy() for o in mx.nd._gdn_step(
        *_nd(data, *small, conv0, gdn0, slot), **ATTRS))
    assert y.shape == (4, 1, D_INNER) and np.isfinite(y).all()
    for row, s in ((0, 3), (1, 0)):
        want_y, want_conv, want_state = _np_mixer(
            data[row], small, conv0[s], states[s].astype(np.float32))
        _close(y[row], want_y, rtol=2e-5)
        _close(conv[s], want_conv, rtol=1e-6)
        _close(gdn[s], _stored(want_state), rtol=2e-5)
    assert np.array_equal(conv[1:3], conv0[1:3])
    assert np.array_equal(gdn[1:3], gdn0[1:3])
    assert not np.array_equal(gdn[4], gdn0[4])


# ----------------------------------------------------------------------
# the model against the plain reference
# ----------------------------------------------------------------------


def test_the_spec_names_three_kinds_of_entry_in_layer_order():
    """Conv windows, delta-rule states and KV rings, each layer's as its
    kind declares them; the serving graphs thread exactly these."""
    lm = family.model(CONFIG)
    spec = lm.cache_spec(4, 48)
    assert list(spec) == ["conv_state_0", "gdn_state_0", "k_cache_1",
                          "v_cache_1", "conv_state_2", "gdn_state_2",
                          "conv_state_3", "gdn_state_3"]
    assert spec["k_cache_1"] == ("ring", (4, 4, 16, 48))
    assert spec["conv_state_0"] == ("state", (4, 3, CONV_DIM))
    # the key axis leading, every head's values side by side
    assert spec["gdn_state_3"] == ("state", (4, DK, H * DV))
    assert spec["gdn_state_3"].nbytes == 4 * 4 * DK * H * DV
    assert len({e.shape for e in spec.values()}) == 3
    shapes = dict(data=(2, 1), slot=(2,), length=(2,), last_token=(4,),
                  **{n: e.shape for n, e in spec.items()})
    for graph in (lm.decode_symbol(), lm.prefill_symbol()):
        assert set(spec) < set(graph.list_arguments())
        _, outs, _ = graph.infer_shape(**shapes)
        assert outs[1:1 + len(spec)] == [e.shape for e in spec.values()]
        shapes.update(data=(1, 8), slot=(1,), length=(1,))
    with pytest.raises(ValueError):  # the kind's sizes are not optional
        TransformerLM(vocab=8, num_layers=1, layer_types=["linear_attention"])
    with pytest.raises(ValueError):
        TransformerLM(vocab=8, block_norm="neither")
    # at the published sizes the state is 96 x (30 x 192): whole TPU tiles
    from benchmarks.harness import spec as bench_spec
    real = bench_spec.Cell(bench_spec.load_benchmark(),
                           "olmohybrid_extract_c16").config
    entry = family.model(real).cache_spec(9, 2304)["gdn_state_0"]
    assert entry.shape == (9, 96, 5760) and 5760 % 128 == 0 and 96 % 8 == 0


@pytest.mark.parametrize("length", [2, CHUNK, 2 * CHUNK + 5])
def test_score_symbol_matches_the_reference(params, held, length):
    tokens = np.random.RandomState(length).randint(0, 40, length)
    _close(_score(family.model(CONFIG), held, tokens),
           reference.logits(params, CONFIG, tokens))


@pytest.mark.parametrize("part", ["beta_2", "l2_norm", "gate", "output_norm",
                                  "norm_placement", "conv_tap"])
def test_the_reference_is_sensitive_to_every_part(params, held, part,
                                                  monkeypatch):
    """Each part the acceptance list names moves the reference's logits
    by far more than RTOL at these weights, so the comparisons of this
    file would see it dropped: the 2 of `beta`, the L2 norm of q and k,
    the gate, the per-head output norm, a conv tap — and the model under
    test with its norms on the branches' INPUTS is far from it."""
    tokens = np.random.RandomState(1).randint(0, 40, 21)
    base = np.asarray(reference.logits(params, CONFIG, tokens))

    def moved(got):
        return np.abs(np.asarray(got) - base).max() / np.abs(base).max()

    if part == "norm_placement":
        lm = family.model(CONFIG)
        pre = TransformerLM(**{**_arguments(lm), "block_norm": "input"})
        assert moved(_score(pre, held, tokens)) > 30 * RTOL
        return
    config, changed = CONFIG, params
    if part == "beta_2":
        config = dict(CONFIG, linear_allow_neg_eigval=False)
    elif part == "conv_tap":
        taps = np.asarray(params["l0_conv_weight"]).copy()
        taps[1] = 0
        changed = dict(params, l0_conv_weight=taps)
    else:
        drop = {"l2_norm": ("_l2", lambda x: x),
                "gate": ("_gated_norm", lambda o, z, gamma, eps:
                         reference._rms(o, gamma, eps)),
                "output_norm": ("_gated_norm", lambda o, z, gamma, eps:
                                o * reference.jax.nn.silu(z))}[part]
        monkeypatch.setattr(reference, *drop)
        reference._linear_layer.clear_cache()   # traced with the part in
    try:
        assert moved(reference.logits(changed, config, tokens)) > 30 * RTOL
    finally:
        monkeypatch.undo()
        reference._linear_layer.clear_cache()


def _arguments(lm):
    """The constructor arguments of a `TransformerLM`, read back (the
    kinds' sizes are in `kind_specs`)."""
    import inspect

    return {n: getattr(lm, n) for n, p in
            inspect.signature(TransformerLM.__init__).parameters.items()
            if n != "self" and p.kind is not p.VAR_KEYWORD}


def test_two_interleaved_sessions_match_one_full_forward(params, held):
    """Two sessions on slots 2 and 0, prefilled in different buckets and
    decoded in turn, one row a step: every call's logits are the row of
    ONE full forward of the reference over that session's sequence."""
    rng = np.random.RandomState(7)
    seqs = [rng.randint(0, 40, 29), rng.randint(0, 40, 16)]
    starts, slots, buckets = [19, 6], [2, 0], [32, 8]
    want = [np.asarray(reference.logits(params, CONFIG, s)) for s in seqs]
    gs = _session(held)
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


def _greedy(params, prompt, budget, width=32):
    """The reference's greedy continuation.  Every call runs `width`
    positions (one compiled shape): the forward is causal, so the row of
    the last real token does not see the zeros behind it."""
    toks = list(prompt)
    for _ in range(budget):
        padded = toks + [0] * (width - len(toks))
        toks.append(int(np.argmax(np.asarray(
            reference.logits(params, CONFIG, padded))[len(toks) - 1])))
    return toks[len(prompt):]


def test_the_batcher_serves_the_references_greedy_tokens(params, held):
    """Five requests of mixed lengths and budgets through two slots — the
    packed decode bucket, the run-ahead token feed, retirements and
    admissions into freed slots — give the reference's greedy tokens."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 40, n).tolist() for n in (9, 2, 17, 5, 12)]
    budgets = [6, 9, 3, 7, 5]
    gs = _session(held, max_sessions=2)
    try:
        reqs = [GenerateRequest("lm", p, 60.0, b)
                for p, b in zip(prompts, budgets)]
        results = _drive(gs, reqs)
    finally:
        gs.close()
    for p, b, r in zip(prompts, budgets, results):
        assert r.tokens.tolist() == _greedy(params, p, b), p


def test_a_reused_slot_gives_what_a_fresh_server_gives(params, held):
    """One slot: a long session (23 tokens in the 32 bucket, 9 steps),
    then a short one (3 tokens in the 8 bucket: fewer than a chunk, as
    many as the conv window) on the slot it left — the short one's logits
    are those of a server that never saw the long one, and the
    reference's."""
    rng = np.random.RandomState(11)
    long_p, short_p = rng.randint(0, 40, 23), rng.randint(0, 40, 3)
    used, fresh = _session(held, max_sessions=1), _session(held,
                                                          max_sessions=1)
    try:
        family._generate(used, long_p, 32, 9)
        got, toks = family._generate(used, short_p, 8, 9)
        alone, toks_alone = family._generate(fresh, short_p, 8, 9)
    finally:
        used.close()
        fresh.close()
    assert toks == toks_alone
    assert np.abs(got - alone).max() <= 1e-6 * np.abs(alone).max()
    _close(got, np.asarray(reference.logits(params, CONFIG, toks))[2:])


def test_the_check_of_the_benchmark_passes_and_refuses_wrong_models(params,
                                                                   held):
    """`check_against_reference` as the cell runs it, on the tiny ladder:
    the model passes far inside all four limits; the same weights under a
    model whose `beta` stops at 1 do not pass — layer 0 is a delta-rule
    layer, so its state shows it before the logits do."""
    gs = _session(held, max_len=64, seq_buckets=[8, 16, 32])
    try:
        ok, facts = family.check_against_reference(CONFIG, gs, params, 3, 8)
    finally:
        gs.close()
    assert ok and facts["prompts"] == 4 and facts["logit_rel_err"] < RTOL
    assert set(facts["by_prompt"]) == {"23_in_32", "2_in_8", "5_in_8",
                                       "9_in_16"}
    # the long prompt decodes until its ring is full, the others 8 steps
    assert facts["steps"] == [41, 8, 8, 8]
    assert set(facts["prefill_state"]) == set(facts["decode_state"]) == set(
        facts["by_prompt"])
    assert facts["prefill_state_rel_err"] < 1e-5 and facts["not_as_stated"] == []
    assert facts["decode_state_rel_err"] < 1e-5
    wrong = dict(CONFIG, linear_allow_neg_eigval=False)
    gs = GenerativeSession("lm", family.model(wrong), held, max_sessions=3,
                           max_len=64, seq_buckets=[8, 16, 32])
    try:
        ok, facts = family.check_against_reference(CONFIG, gs, params, 3, 8)
    finally:
        gs.close()
    assert not ok and facts["logit_rel_err"] > family.LOGIT_RTOL
    assert facts["prefill_state_rel_err"] > family.PREFILL_STATE_RTOL


def _check(gs, params):
    try:
        return family.check_against_reference(CONFIG, gs, params, 3, 8)
    finally:
        gs.close()


def test_the_check_refuses_a_lower_precision_than_the_configuration_states(
        params, held, monkeypatch):
    """What logits against a float32 forward cannot tell from the
    projections' own bfloat16 pass on the chip, the other limits refuse:
    weights rounded once to bfloat16 (limit 1, by what the tenant holds),
    a state buffer kept in bfloat16 (limit 1), and a delta rule that
    rounds its state to bfloat16 at every call (limits 2, 3: layer 0's
    state against the reference's)."""
    import jax.numpy as jnp

    rounded = {k: mx.nd.array(np.asarray(jnp.asarray(v).astype(
        jnp.bfloat16).astype(jnp.float32))) for k, v in params.items()}
    ok, facts = _check(_session(rounded, max_len=64, seq_buckets=[8, 16, 32]),
                       params)
    assert not ok and "embed_weight" in facts["not_as_stated"]

    gs = _session(held, max_len=64, seq_buckets=[8, 16, 32])
    at = list(gs._spec).index("gdn_state_2")
    run = gs._run

    def keep_one_buffer_in_bfloat16(*args):
        out = run(*args)
        gs._state[at] = jnp.asarray(gs._state[at], jnp.bfloat16)
        return out

    monkeypatch.setattr(gs, "_run", keep_one_buffer_in_bfloat16)
    ok, facts = _check(gs, params)
    assert not ok and facts["not_as_stated"] == ["gdn_state_2"]

    gs = _session(held, max_len=64, seq_buckets=[8, 16, 32])
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
    ok, facts = _check(gs, params)
    assert not ok and facts["not_as_stated"] == []
    assert (facts["prefill_state_rel_err"] > family.PREFILL_STATE_RTOL
            or facts["decode_state_rel_err"] > family.DECODE_STATE_RTOL)
    assert facts["decode_state_rel_err"] > 1e3 * 1e-6  # sound: < 1e-6


# ----------------------------------------------------------------------
# training graph
# ----------------------------------------------------------------------


def test_training_loss_and_gradients_match_jax_grad_of_the_reference(params):
    """`training_symbol` bound for gradients: the loss is the reference's
    mean next-token cross-entropy and every parameter's gradient is
    `jax.grad` of it — the chunk's triangular solve, the L2 norms, the
    gated norm and the output-normed block differentiate as the
    position-by-position recurrence does."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    t = 2 * CHUNK + 3
    tokens = rng.randint(0, 40, (1, t))
    labels = rng.randint(0, 40, (1, t))
    net = family.model(CONFIG).training_symbol()
    exe = net.simple_bind(mx.cpu(), grad_req="write", data=(1, t),
                          softmax_label=(1, t))
    for name, arr in exe.arg_dict.items():
        if name in params:
            arr[:] = np.asarray(params[name])
    exe.arg_dict["data"][:] = tokens
    exe.arg_dict["softmax_label"][:] = labels
    exe.forward(is_train=True)
    probs = exe.outputs[0].asnumpy()
    exe.backward()

    def loss(p):
        logp = jax.nn.log_softmax(reference.logits(p, CONFIG, tokens[0]))
        return -jnp.mean(logp[jnp.arange(t), labels[0]])

    want_loss, grads = jax.value_and_grad(loss)(
        {k: jnp.asarray(v) for k, v in params.items()})
    got_loss = -np.mean(np.log(probs[np.arange(t), labels[0]]))
    assert abs(got_loss - float(want_loss)) < 1e-5 * abs(float(want_loss))
    for name in sorted(params):
        got, want = exe.grad_dict[name].asnumpy(), np.asarray(grads[name])
        assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max() + 1e-8, (
            name, np.abs(got - want).max() / np.abs(want).max())


# ----------------------------------------------------------------------
# what the rest of the system must notice
# ----------------------------------------------------------------------


def test_admission_charges_the_specs_bytes(held, monkeypatch):
    """`add_generative_tenant` predicts parameters + EVERY cache entry by
    its own bytes — windows, delta-rule states and rings — and knows no
    kind: with the sum it admits."""
    from mxnet_tpu.obs import memory

    lm = family.model(CONFIG)
    spec = lm.cache_spec(3 + 1, 48)
    cache = sum(e.nbytes for e in spec.values())
    assert cache == 4 * sum(int(np.prod(e.shape)) for e in spec.values())
    param_bytes = sum(memory.nbytes_of(v) for v in held.values())
    seen = []
    monkeypatch.setattr(memory, "admit",
                        lambda what, nbytes, device=None: seen.append(nbytes))
    server = mx.serving.ModelServer({})
    try:
        server.add_generative_tenant("lm", lm, held, ctx=mx.cpu(),
                                     max_sessions=3, max_len=48,
                                     seq_buckets=[8])
    finally:
        server.close()
    # the parameters are on the tenant's device already and what the live
    # census has booked of them is in the bytes `admit` adds: predicted
    # once, not twice (PR 38)
    assert seen == [param_bytes - sum(v._mem_booked for v in held.values())
                    + cache]


def test_the_delta_rule_counters(held):
    """Per prefill `gdn.scan_positions` grows by the bucket (the pad
    included) times the linear layers — and `gdn.kernel_positions` by as
    much where the prefill's program runs the TPU's kernel, by nothing on
    the CPU; per decode dispatch `gdn.state_bytes` by the window and
    state of each real row, read and written, times the linear layers —
    and `gdn.step_kernel_bytes` by as much where the decode program runs
    the TPU's step kernel — the kind declares all four (`TransformerLM.call_counters`) from the
    shapes and the platform it is told, the session books what it is
    told."""
    telemetry.set_enabled(True)
    names = ("gdn.scan_positions", "gdn.kernel_positions", "gdn.state_bytes",
             "gdn.step_kernel_bytes", "serving.decode.dispatches",
             "cache.state_bytes")
    before = {n: telemetry.counter_value(n) for n in names}
    gs = _session(held, max_sessions=2)
    try:
        reqs = [GenerateRequest("lm", list(range(1, 1 + n)), 60.0, 3)
                for n in (5, 11)]
        _drive(gs, reqs)
    finally:
        gs.close()
    moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    page = 4 * (3 * CONV_DIM + DK * H * DV)
    # the second prompt's mixed step carried the first session's row:
    # the same four rows in three dispatches
    assert moved["serving.decode.dispatches"] == 3
    assert moved["gdn.scan_positions"] == 3 * (8 + 32)
    assert moved["gdn.kernel_positions"] == 0       # the CPU's programs
    assert moved["gdn.state_bytes"] == 2 * 2 * 3 * 2 * page
    assert moved["gdn.step_kernel_bytes"] == 0      # the CPU's programs
    assert moved["cache.state_bytes"] > 0
    lm = family.model(CONFIG)
    attn = {"attn.prefill_positions": 32, "attn.kernel_positions": 0}
    assert lm.call_counters(positions=32, platform="cpu") == {
        **attn, "gdn.scan_positions": 96, "gdn.kernel_positions": 0,
        "gdn.state_bytes": 0, "gdn.step_kernel_bytes": 0}
    # a program lowered for the TPU runs the kernel in every bucket of
    # whole chunks (of 8 here), and the body in any other
    assert lm.call_counters(positions=32, platform="tpu") == {
        **attn, "gdn.scan_positions": 96, "gdn.kernel_positions": 96,
        "gdn.state_bytes": 0, "gdn.step_kernel_bytes": 0}
    assert lm.call_counters(positions=36, platform="tpu") == {
        "attn.prefill_positions": 36, "attn.kernel_positions": 0,
        "gdn.scan_positions": 108, "gdn.kernel_positions": 0,
        "gdn.state_bytes": 0, "gdn.step_kernel_bytes": 0}
    assert lm.call_counters(rows=2, platform="tpu") == {
        "attn.prefill_positions": 0, "attn.kernel_positions": 0,
        "gdn.scan_positions": 0, "gdn.kernel_positions": 0,
        "gdn.state_bytes": 2 * 2 * 3 * page, "gdn.step_kernel_bytes": 0}
    # (four heads of 16 values fill no lane tile: `ops.gdn.step_heads`); at
    # the published widths a program lowered for the TPU steps every page
    # through the kernel, and none lowered for the CPU does
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        real = family.model(json.load(f))
    stepped = real.call_counters(rows=8, platform="tpu")
    assert stepped["gdn.step_kernel_bytes"] == stepped["gdn.state_bytes"] > 0
    assert real.call_counters(rows=8, platform="cpu")[
        "gdn.step_kernel_bytes"] == 0
    # a plain decoder books its attention layers' positions and no other
    assert TransformerLM(vocab=8).call_counters(positions=32, rows=4) == {
        "attn.prefill_positions": 2 * 32, "attn.kernel_positions": 0}
