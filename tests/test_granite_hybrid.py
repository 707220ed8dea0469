"""A hybrid decoder through `TransformerLM` and `GenerativeSession`:
Granite 4.0-H's block — Mamba-2 mixers with one grouped-query NoPE
attention mixer among them, a dense SwiGLU MLP in every layer, RMSNorm,
the four multipliers — against the plain reference of the benchmark
(benchmarks/reference/granite_hybrid.py: float32 `jax.numpy` at
"highest", the recurrence a `lax.scan` over positions, independent of
`mxnet_tpu`).

Tiny widths (4 layers `[mamba, attention, mamba, mamba]`, hidden 64, 4
Mamba heads x 16, 16 states, chunk 8, 4 query / 2 K/V heads), both sides
float32 on the CPU: errors are float32 rounding (measured 2e-7 of the
largest logit through the state); the bound 1e-4 is far above that and a
fortieth of what one bfloat16 pass leaves.  The file costs about 45 s.
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
from benchmarks.families import granite_hybrid as family  # noqa: E402
from benchmarks.reference import granite_hybrid as reference  # noqa: E402

CONFIG = {"vocab_size": 40, "hidden_size": 64, "num_hidden_layers": 4,
          "layer_types": ["mamba", "attention", "mamba", "mamba"],
          "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
          "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "shared_intermediate_size": 96, "rms_norm_eps": 1e-5,
          "embedding_multiplier": 12, "residual_multiplier": 0.22,
          "attention_multiplier": 0.0625, "logits_scaling": 8,
          "tie_word_embeddings": True, "max_position_embeddings": 64,
          "param_dtype": "float32", "state_dtype": "float32"}
RTOL = 1e-4  # of the largest |logit|; see the module docstring
CHUNK = CONFIG["mamba_chunk_size"]


def _params(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # conv's bias and the gains; x5 makes every part of the block matter
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
# the state-space ops alone, against a position-by-position recurrence
# ----------------------------------------------------------------------

H, P, S, G, K = 4, 8, 16, 2, 4
D_INNER, CONV_DIM = H * P, H * P + 2 * G * S
ATTRS = dict(num_heads=H, head_dim=P, state_size=S, n_groups=G,
             conv_kernel=K, chunk_size=CHUNK, eps=1e-5)


def _mixer_inputs(n, t, seed):
    rng = np.random.RandomState(seed)
    data = rng.randn(n, t, D_INNER + CONV_DIM + H).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-2), np.log(0.5), H))
    small = [rng.uniform(-0.5, 0.5, (K, CONV_DIM)),       # conv_weight
             rng.uniform(-0.5, 0.5, (CONV_DIM,)),         # conv_bias
             dt + np.log(-np.expm1(-dt)),                 # dt_bias
             np.log(rng.uniform(1, 8, H)),                # A_log
             1 + 0.1 * rng.randn(H),                      # D
             1 + 0.1 * rng.randn(D_INNER)]                # norm_gamma
    return data, [v.astype(np.float32) for v in small]


def _silu(x):
    return x / (1 + np.exp(-x))


def _np_mixer(data, small, conv=None, state=None):
    """The Mamba-2 mixer one position at a time, float64: returns (y,
    conv window, state) after the last position."""
    w, bias, dt_bias, a_log, d_skip, gamma = (v.astype(np.float64)
                                              for v in small)
    conv = np.zeros((K - 1, CONV_DIM)) if conv is None else conv.copy()
    state = np.zeros((H, P, S)) if state is None else state.copy()
    ys = []
    for row in data.astype(np.float64):
        z, xbc, dt = np.split(row, [D_INNER, D_INNER + CONV_DIM])
        window = np.concatenate([conv, xbc[None]])
        conv = window[1:]
        xbc = _silu((window * w).sum(0) + bias)
        x = xbc[:D_INNER].reshape(H, P)
        b = np.repeat(xbc[D_INNER:D_INNER + G * S].reshape(G, S), H // G, 0)
        c = np.repeat(xbc[D_INNER + G * S:].reshape(G, S), H // G, 0)
        dt = np.log1p(np.exp(dt + dt_bias))
        state = (np.exp(-dt * np.exp(a_log))[:, None, None] * state
                 + (dt[:, None] * x)[:, :, None] * b[:, None, :])
        y = (state * c[:, None, :]).sum(-1) + d_skip[:, None] * x
        # gate first, then each GROUP's channels on their own mean square
        y = (y.reshape(D_INNER) * _silu(z)).reshape(G, -1)
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
        ys.append(y.reshape(D_INNER) * gamma)
    return np.stack(ys), conv, state


def _nd(*arrays):
    return [mx.nd.array(np.asarray(a, np.float32)) for a in arrays]


@pytest.mark.parametrize("length", [1, 3, CHUNK - 1, CHUNK, CHUNK + 1,
                                    2 * CHUNK, 3 * CHUNK + 5])
def test_chunked_scan_matches_the_recurrence(length):
    """`_ssm_scan` (chunk 8, two groups of B/C) against the mixer run one
    position at a time, at lengths below, at and across chunk
    boundaries."""
    data, small = _mixer_inputs(2, length, seed=length)
    got = mx.nd._ssm_scan(*_nd(data, *small), **ATTRS).asnumpy()
    for n in range(2):
        _close(got[n], _np_mixer(data[n], small)[0], rtol=2e-5)


@pytest.mark.parametrize("length", [1, 2, 3, CHUNK, 11, 2 * CHUNK + 3])
def test_padded_prefill_leaves_the_state_of_the_true_length(length):
    """`_ssm_prefill` in a bucket of 24 with `length` true positions: the
    outputs up to `length`, the conv window and the state are those of an
    unpadded run of the true length (1 and 2 are shorter than the window:
    zeros before the sequence), written at `slot` over whatever the slot
    held, and no other slot is touched."""
    bucket, slots, slot = 3 * CHUNK, 4, 2
    data, small = _mixer_inputs(1, bucket, seed=20 + length)
    rng = np.random.RandomState(length)
    conv0 = rng.randn(slots, K - 1, CONV_DIM).astype(np.float32)
    ssm0 = rng.randn(slots, H, P, S).astype(np.float32)
    y, conv, ssm = (o.asnumpy() for o in mx.nd._ssm_prefill(
        *_nd(data, *small, conv0, ssm0, [slot], [length]), **ATTRS))
    want_y, want_conv, want_ssm = _np_mixer(data[0, :length], small)
    _close(y[0, :length], want_y, rtol=2e-5)
    _close(conv[slot], want_conv, rtol=1e-6)
    _close(ssm[slot], want_ssm, rtol=2e-5)
    others = [i for i in range(slots) if i != slot]
    assert np.array_equal(conv[others], conv0[others])
    assert np.array_equal(ssm[others], ssm0[others])


def test_decode_step_advances_each_rows_slot_and_pads_dirty_only_scratch():
    """`_ssm_step` for two live rows at slots 3 and 0 and two padded rows
    at the scratch slot 4: each live row continues ITS slot's window and
    state by one position; slots 1 and 2 are bit-for-bit untouched; only
    the scratch slot takes the padded rows' garbage."""
    slots = 5
    data, small = _mixer_inputs(4, 1, seed=9)
    rng = np.random.RandomState(2)
    conv0 = rng.randn(slots, K - 1, CONV_DIM).astype(np.float32)
    ssm0 = rng.randn(slots, H, P, S).astype(np.float32)
    slot = [3, 0, 4, 4]
    y, conv, ssm = (o.asnumpy() for o in mx.nd._ssm_step(
        *_nd(data, *small, conv0, ssm0, slot), **ATTRS))
    assert y.shape == (4, 1, D_INNER) and np.isfinite(y).all()
    for row, s in ((0, 3), (1, 0)):
        want_y, want_conv, want_ssm = _np_mixer(data[row], small, conv0[s],
                                                ssm0[s])
        _close(y[row], want_y, rtol=2e-5)
        _close(conv[s], want_conv, rtol=1e-6)
        _close(ssm[s], want_ssm, rtol=2e-5)
    assert np.array_equal(conv[1:3], conv0[1:3])
    assert np.array_equal(ssm[1:3], ssm0[1:3])
    assert not np.array_equal(ssm[4], ssm0[4])


# ----------------------------------------------------------------------
# the model against the plain reference
# ----------------------------------------------------------------------


def test_the_spec_states_both_kinds_of_state_in_layer_order():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(4, 48)
    assert list(spec) == ["conv_state_0", "ssm_state_0", "k_cache_1",
                          "v_cache_1", "conv_state_2", "ssm_state_2",
                          "conv_state_3", "ssm_state_3"]
    assert spec["k_cache_1"] == ("ring", (4, 2, 16, 48))   # K/V heads: 2
    assert spec["conv_state_0"] == ("state", (4, 3, 64 + 2 * 16))
    assert spec["ssm_state_3"] == ("state", (4, 4, 16, 16))
    assert spec["ssm_state_3"].nbytes == 4 * 4 * 4 * 16 * 16
    names = list(spec)
    shapes = dict(data=(2, 1), slot=(2,), length=(2,), last_token=(4,),
                  **{n: e.shape for n, e in spec.items()})
    for graph in (lm.decode_symbol(), lm.prefill_symbol()):
        assert set(names) < set(graph.list_arguments())
        _, outs, _ = graph.infer_shape(**shapes)
        assert outs[1:1 + len(spec)] == [e.shape for e in spec.values()]
        shapes.update(data=(1, 8), slot=(1,), length=(1,))
    with pytest.raises(ValueError):
        TransformerLM(vocab=8, num_layers=2, layer_types=["mamba"])
    with pytest.raises(ValueError):
        TransformerLM(vocab=8, num_layers=1, layer_types=["mamba"])


@pytest.mark.parametrize("length", [2, CHUNK, 2 * CHUNK + 5])
def test_score_symbol_matches_the_reference(params, held, length):
    tokens = np.random.RandomState(length).randint(0, 40, length)
    _close(_score(family.model(CONFIG), held, tokens),
           reference.logits(params, CONFIG, tokens))


def test_the_reference_is_sensitive_to_every_part(params):
    """Each term the acceptance list names moves the reference's logits
    by far more than RTOL at these weights, so the comparisons above
    would see it dropped: the conv bias, `D`, `dt_bias`, the gated
    norm's gain, and each of the four multipliers."""
    tokens = np.random.RandomState(1).randint(0, 40, 21)
    base = np.asarray(reference.logits(params, CONFIG, tokens))

    def moved(params=params, config=CONFIG):
        got = np.asarray(reference.logits(params, config, tokens))
        return np.abs(got - base).max() / np.abs(base).max()

    for name in ("l0_conv_bias", "l0_D", "l0_dt_bias"):
        assert moved(dict(params, **{name: 0 * params[name]})) > 30 * RTOL
    assert moved(dict(params, l0_mnorm_gamma=1 + 0 * params["l0_mnorm_gamma"])
                 ) > 30 * RTOL
    for key, other in (("embedding_multiplier", 1), ("logits_scaling", 1),
                       ("residual_multiplier", 1.0),
                       ("attention_multiplier", 0.25)):
        assert moved(config=dict(CONFIG, **{key: other})) > 30 * RTOL, key


def test_two_interleaved_sessions_match_one_full_forward(params, held):
    """Prefill (padded buckets: 11 in 32, 5 in 8) then ten decode steps of
    two sessions, one step of each in turn, through the session's own
    programs and state: every call's logits are the reference's at that
    position of that session's sequence."""
    rng = np.random.RandomState(7)
    seqs = [rng.randint(0, 40, 21), rng.randint(0, 40, 15)]
    starts, slots, buckets = [11, 5], [2, 0], [32, 8]
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
    then a short one (3 tokens in the 8 bucket) on the slot it left — the
    short one's logits are those of a server that never saw the long one,
    and the reference's."""
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


def test_padded_decode_rows_dirty_only_the_scratch_slot(held):
    """Three live sessions in the four-row decode bucket: the padded row
    points at the scratch slot.  After a step the scratch slot's state
    has changed and the free slot's (slot 3: never admitted) has not,
    in every state entry."""
    gs = _session(held, max_sessions=4)
    try:
        reqs = [GenerateRequest("lm", [3 + i, 1, 4], 60.0, 4)
                for i in range(3)]
        assert gs.admit(reqs) == []
        gs.decode_step()
        before = [np.asarray(s) for s in gs._state]
        assert gs.decode_step() == 3
        gs.decode_step()
        after = [np.asarray(s) for s in gs._state]
        kinds = [e.kind for e in gs._spec.values()]
        free, scratch = gs._free[0], gs._slots
        assert free == 0  # the LIFO pool handed out 3, 2, 1
        for kind, b, a in zip(kinds, before, after):
            assert np.array_equal(a[free], b[free])
            if kind == "state":
                assert not np.array_equal(a[scratch], b[scratch])
        while gs.active():
            gs.decode_step()
    finally:
        gs.close()


def test_the_check_of_the_benchmark_passes_and_refuses_wrong_models(params,
                                                                   held):
    """`check_against_reference` as the cell runs it, on the tiny ladder:
    the model passes far inside all four limits; the same weights under
    a model with no attention scale at all (1 in place of 1/16) do not
    pass the logits'."""
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
    wrong = dict(CONFIG, attention_multiplier=1.0)
    gs = GenerativeSession("lm", family.model(wrong), held, max_sessions=3,
                           max_len=64, seq_buckets=[8, 16, 32])
    try:
        ok, facts = family.check_against_reference(CONFIG, gs, params, 3, 8)
    finally:
        gs.close()
    assert not ok and facts["logit_rel_err"] > family.LOGIT_RTOL
    # layer 0 is a Mamba layer: its state never saw the attention scale
    assert facts["decode_state_rel_err"] < 1e-5


def _check(gs, params):
    try:
        return family.check_against_reference(CONFIG, gs, params, 3, 8)
    finally:
        gs.close()


def test_the_check_refuses_a_lower_precision_than_the_configuration_states(
        params, held, monkeypatch):
    """What logits against a float32 forward cannot tell from the
    projections' own bfloat16 pass on the chip, the other two limits
    refuse: weights rounded once to bfloat16 (limit 1, by what the
    tenant holds), a state buffer kept in bfloat16 (limit 1), and a
    recurrence that rounds its state to bfloat16 at every call (limits 2, 3:
    layer 0's state against the reference's)."""
    import jax.numpy as jnp

    rounded = {k: mx.nd.array(np.asarray(jnp.asarray(v).astype(
        jnp.bfloat16).astype(jnp.float32))) for k, v in params.items()}
    ok, facts = _check(_session(rounded, max_len=64, seq_buckets=[8, 16, 32]),
                       params)
    assert not ok and "embed_weight" in facts["not_as_stated"]

    gs = _session(held, max_len=64, seq_buckets=[8, 16, 32])
    at = list(gs._spec).index("ssm_state_2")
    run = gs._run

    def keep_one_buffer_in_bfloat16(*args):
        out = run(*args)
        gs._state[at] = jnp.asarray(gs._state[at], jnp.bfloat16)
        return out

    monkeypatch.setattr(gs, "_run", keep_one_buffer_in_bfloat16)
    ok, facts = _check(gs, params)
    assert not ok and facts["not_as_stated"] == ["ssm_state_2"]

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
    `jax.grad` of it — the scan, the gated norm and the grouped heads
    differentiate as plain `jax.numpy` does."""
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
# what the rest of the system must not notice, and what it must
# ----------------------------------------------------------------------

def test_admission_charges_the_specs_bytes(held, monkeypatch):
    """`add_generative_tenant` predicts parameters + EVERY cache entry by
    its own bytes: with a budget one byte under that sum it refuses
    (naming the sum), with the sum itself it admits."""
    from mxnet_tpu.obs import memory

    lm = family.model(CONFIG)
    spec = lm.cache_spec(3 + 1, 48)
    cache = sum(e.nbytes for e in spec.values())
    assert cache == 4 * sum(int(np.prod(e.shape)) for e in spec.values())
    assert len({e.shape for e in spec.values()}) == 3   # not one shape
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


def test_the_cache_and_prefill_counters(held):
    """Per decode dispatch `cache.reserved_bytes` grows by both bound
    sets' bytes (the live one and the placeholder the programs share) and `cache.state_bytes` by their recurrent part; per
    prefill `serving.prefill.bucket_positions` / `.pad_positions` by the
    bucket and its pad; `kv.*` keep counting ring positions."""
    telemetry.set_enabled(True)
    names = ("cache.reserved_bytes", "cache.state_bytes",
             "serving.prefill.bucket_positions",
             "serving.prefill.pad_positions", "kv.reserved_positions",
             "kv.used_positions", "serving.decode.dispatches")
    before = {n: telemetry.counter_value(n) for n in names}
    gs = _session(held, max_sessions=2)
    try:
        reqs = [GenerateRequest("lm", list(range(1, 1 + n)), 60.0, 3)
                for n in (5, 11)]
        _drive(gs, reqs)
        assert len(gs._programs) >= 3
        spec = gs._spec
        # PR 59: the bucket programs' executors share ONE zero-filled
        # placeholder for each cache entry — the very same array — where
        # each bound a set of its own
        for name in spec:
            held_by = {id(exe.arg_dict[name]._data)
                       for exe in gs._programs.values()}
            assert len(held_by) == 1, name
    finally:
        gs.close()
    moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    steps = moved["serving.decode.dispatches"]
    cache = sum(e.nbytes for e in spec.values())
    state = sum(e.nbytes for e in spec.values() if e.kind == "state")
    assert steps == 2 and 0 < state < cache
    # the live set and the one placeholder set, however many programs
    assert moved["cache.reserved_bytes"] == steps * 2 * cache
    assert (moved["cache.state_bytes"] * cache
            == moved["cache.reserved_bytes"] * state)
    assert moved["serving.prefill.bucket_positions"] == 8 + 32
    assert moved["serving.prefill.pad_positions"] == (8 - 5) + (32 - 11)
    assert moved["kv.used_positions"] == (5 + 11) + (6 + 12)
    assert moved["kv.reserved_positions"] * cache == (
        moved["cache.reserved_bytes"] * 3 * 48)
    assert telemetry.snapshot()["gauges"]["kv.ring_bytes"] == cache + 4 * 3


def test_a_mamba_model_keeps_its_two_programs(held):
    """PR 46: `_Mamba2` has no mixed form, so Granite's model offers no
    mixed graph and its session is the parent's: `admit` dispatches the
    prefill itself and leaves nothing pending, the bound programs are the
    prefill buckets' and the decode ladder's with no rider operand, no
    decode bucket is built ahead of its first use, and the two counters of
    the mixed step are booked by 0 with every admission."""
    lm = family.model(CONFIG)
    assert lm.mixed_symbol(3) is None
    telemetry.set_enabled(True)
    names = ("serving.prefill.mixed", "serving.prefill.rider_rows",
             "serving.decode.sessions", "serving.decode.bucket_programs")
    before = {n: telemetry.counter_value(n) for n in names}
    gs = _session(held)
    try:
        assert not gs._mixed
        reqs = [GenerateRequest("lm", list(range(1, 1 + n)), 60.0, 4)
                for n in (5, 11)]
        assert gs.admit(reqs) == [] and not gs._pending
        assert [f.prog.kind for f in gs._flights] == ["prefill"] * 2
        assert set(gs._programs) == {("prefill", 8), ("prefill", 32)}
        _drive(gs, [])
        assert set(gs._programs) == {("prefill", 8), ("prefill", 32),
                                     ("decode", 2)}
        assert not any("row_data" in exe.arg_dict
                       for exe in gs._programs.values())
    finally:
        gs.close()
    moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    assert moved == {"serving.prefill.mixed": 0,
                     "serving.prefill.rider_rows": 0,
                     "serving.decode.sessions": 2,
                     "serving.decode.bucket_programs": 3}
    for r in reqs:
        assert len(r.future.result(timeout=5).tokens) == 4


def test_a_model_with_no_ring_reads_no_kv_counter(params):
    """All-Mamba layers: the session holds state only, and the `kv.*`
    position counters stay where they were."""
    config = dict(CONFIG, num_hidden_layers=2, layer_types=["mamba", "mamba"])
    held = _hold(_params(config))
    telemetry.set_enabled(True)
    before = {n: telemetry.counter_value(n)
              for n in ("kv.reserved_positions", "kv.used_positions",
                        "cache.state_bytes", "cache.reserved_bytes")}
    gs = _session(held, config=config, max_sessions=2)
    try:
        res, = _drive(gs, [GenerateRequest("lm", [1, 2, 3], 60.0, 4)])
    finally:
        gs.close()
    assert len(res.tokens) == 4
    moved = {n: telemetry.counter_value(n) - before[n] for n in before}
    assert moved["kv.reserved_positions"] == moved["kv.used_positions"] == 0
    assert moved["cache.state_bytes"] == moved["cache.reserved_bytes"] > 0
