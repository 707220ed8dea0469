"""Trinity's block through `TransformerLM` and `GenerativeSession`:
sliding-window layers (rotary) whose KV rings are W positions and wrap,
beside a full layer (no position signal) whose rings are the session's
length; 4 query heads over 2 K/V heads of a head width the model states;
per-head QK-norm; a sigmoid gate on attention's output; both ends of
every branch normed; one dense SwiGLU layer and then sigmoid-routed
experts with a selection bias, renormalised and scaled weights and a
shared expert, of which this model holds a range — against the plain
reference of the benchmark (benchmarks/reference/afmoe.py: float32
`jax.numpy` at "highest", no cache, independent of `mxnet_tpu`).

Tiny widths (5 layers, hidden 32, heads of 16, window 8, 8 experts of
which 4 are held, 2 a token), both sides float32 on the CPU: errors are
float32 rounding (measured 1e-6 of the largest logit); the bound 1e-4 is
far above that and a fortieth of what one bfloat16 pass leaves.  The file
costs about 50 s.
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
from benchmarks.families import afmoe as family  # noqa: E402
from benchmarks.reference import afmoe as reference  # noqa: E402

W = 8
CONFIG = {"vocab_size": 67, "hidden_size": 32, "head_dim": 16,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "intermediate_size": 48, "moe_intermediate_size": 24,
          "num_hidden_layers": 5, "num_dense_layers": 1,
          "layer_types": ["sliding_attention", "sliding_attention",
                          "full_attention", "sliding_attention",
                          "sliding_attention"],
          "sliding_window": W, "num_experts": 4, "router_experts": 8,
          "held_experts": [0, 4], "num_experts_per_tok": 2,
          "num_shared_experts": 1, "score_func": "sigmoid",
          "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
          "rms_norm_eps": 1e-5, "rope_theta": 10000,
          "tie_word_embeddings": False, "max_position_embeddings": 96,
          "param_dtype": "float32"}
UNCUT = dict(CONFIG, num_experts=8, held_experts=[0, 8])
RTOL = 1e-4  # of the largest |logit|; see the module docstring


def _params(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x10 makes every part of the block matter, and spreads the
    # router's logits and its bias over a few units
    p = family.make_params(config, seed, jax.devices("cpu")[0])
    return {k: 10.0 * v if not k.endswith("_gamma") else v
            for k, v in p.items()}


@pytest.fixture(scope="module")
def uncut():
    return _params(UNCUT)


def _share(params, first, count):
    """The parameters of the chip that holds experts `first` .. `first +
    count` of the uncut model's."""
    cut = ("_gate_weight", "_up_weight", "_down_weight")
    return {k: v[first:first + count]
            if k.endswith(cut) and "shared" not in k else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def params(uncut):
    return _share(uncut, 0, 4)


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
    kw = dict(dict(max_sessions=3, max_len=64, max_decode_tokens=40,
                   seq_buckets=[8, 32]), **kw)
    return GenerativeSession("lm", family.model(config), held, **kw)


TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 67, 40)]


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "other_share", "uncut"])
def test_score_symbol_matches_the_reference(which, uncut):
    """The full-sequence graph, for the share the cell holds, the other
    chip's share, and the uncut model: 40 positions, five windows deep."""
    config, p = {"share": (CONFIG, _share(uncut, 0, 4)),
                 "other_share": (dict(CONFIG, held_experts=[4, 4]),
                                 _share(uncut, 4, 4)),
                 "uncut": (UNCUT, uncut)}[which]
    got = _score(family.model(config), _hold(p), TOKENS)
    _close(got, reference.logits(p, config, TOKENS))


@pytest.mark.parametrize("prompt,bucket", [(5, 8), (8, 8), (20, 32),
                                           (31, 32)])
def test_prefill_then_decode_through_the_rings_matches_the_reference(
        prompt, bucket, params, held):
    """Prefill and then every decode step to position 40 against ONE full
    forward of the reference: a prompt shorter than the window (the ring
    wraps under decode steps), one that fills it exactly, and two LONGER
    than it (the prefill writes the prompt's last 8 positions, each where
    a decode step would have put it)."""
    session = _session(held)
    try:
        toks, got = TOKENS[:prompt], []
        exe, fn = session._program(session._prefill_pred, 1, bucket, True)
        data = np.zeros((1, bucket), np.float32)
        data[0, :prompt] = toks
        zero = np.zeros((1,), np.float32)
        got.append(session._run(exe, fn, data, zero,
                                np.full((1,), prompt, np.float32))[0])
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        for t in range(prompt, len(TOKENS)):
            got.append(session._run(
                exe, fn, np.asarray([[TOKENS[t]]], np.float32), zero,
                np.full((1,), t, np.float32))[0])
    finally:
        session.close()
    want = np.asarray(reference.logits(params, CONFIG, TOKENS))
    _close(np.asarray(got), want[prompt - 1:])


def test_the_vocabulary_slice_is_a_smaller_vocabulary(params):
    """Rows 0-39 of the embedding and of the head are a model of 40
    tokens: its logits are the first 40 columns of the whole model's."""
    cut = dict(params, embed_weight=params["embed_weight"][:40],
               head_weight=params["head_weight"][:40])
    tokens = [t % 40 for t in TOKENS]
    config = dict(CONFIG, vocab_size=40)
    got = _score(family.model(config), _hold(cut), tokens)
    _close(got, np.asarray(reference.logits(params, CONFIG, tokens))[:, :40])
    _close(got, reference.logits(cut, config, tokens))


# ----------------------------------------------------------------------
# one chip's share of an expert layer
# ----------------------------------------------------------------------

def _expert_layer(p, i, first, count, shared, x):
    """Layer i's `mx.sym.MoE` node alone on `x (T, d)`, holding experts
    `first` .. `first + count`, with or without the shared expert."""
    names = ["router_weight", "router_bias", "gate_weight", "down_weight",
             "up_weight"] + (["shared_gate_weight", "shared_down_weight",
                              "shared_up_weight"] if shared else [])
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    node = mx.sym.MoE(*v, num_experts=8, hidden_size=24, k=2,
                      act_type="silu", gated=True, no_bias=True,
                      normalize=True, score_func="sigmoid", select_bias=True,
                      route_scale=2.826, held_first=first, held_count=count,
                      shared_size=24 if shared else 0, return_load=True)
    values = {n: np.asarray(p["l%d_%s" % (i, n)]) for n in names}
    for n in ("gate_weight", "down_weight", "up_weight"):
        values[n] = values[n][first:first + count]
    exe = node.bind(mx.cpu(), dict({"data": mx.nd.array(x)}, **{
        n: mx.nd.array(a) for n, a in values.items()}), grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


def test_the_two_halves_and_the_shared_expert_once_make_the_uncut_layer(
        uncut):
    """THE SHARE TEST: the outputs of one expert layer held as experts
    0-3 and as experts 4-7 (the router 8 wide, 2 a token, on both), the
    shared expert counted once, add up to what the uncut reference gives
    for the whole layer; and each half's load counts its own experts'
    pairs, which together are every pair."""
    import jax

    x = np.random.default_rng(2).standard_normal((24, 32)).astype(np.float32)
    layer = lambda n: uncut["l2_" + n]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(
            x, layer("router_weight"), layer("router_bias"),
            layer("gate_weight"), layer("up_weight"), layer("down_weight"),
            (layer("shared_gate_weight"), layer("shared_up_weight"),
             layer("shared_down_weight")), 2, 2.826, True, 0)
    low, load_low = _expert_layer(uncut, 2, 0, 4, True, x)
    high, load_high = _expert_layer(uncut, 2, 4, 4, False, x)
    _close(low + high, want, 1e-5)
    assert load_low.shape == load_high.shape == (4,)
    assert load_low.sum() + load_high.sum() == 24 * 2
    assert load_low.sum() > 0 and load_high.sum() > 0
    # neither half is the layer: the other chip's terms are LEFT OUT
    assert np.abs(low - np.asarray(want)).max() > 1e-2 * np.abs(want).max()


def test_rows_beyond_the_held_segments_count_as_zero_whatever_lies_there(
        uncut, monkeypatch):
    """On a TPU a segment matmul leaves stale memory in the rows its
    segments do not cover (on the CPU, zeros — so no CPU run of the share
    test can see a missing mask; measured on the v5e, PR 38).  With NaN
    put there the held layer's output is what it was."""
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.parallel import moe

    x = np.random.default_rng(2).standard_normal((24, 32)).astype(np.float32)
    want, _ = _expert_layer(uncut, 2, 0, 4, True, x)
    real = lax.ragged_dot

    def stale(rows, weights, sizes):
        covered = jnp.arange(rows.shape[0]) < sizes.sum()
        return jnp.where(covered[:, None], real(rows, weights, sizes), jnp.nan)

    monkeypatch.setattr(moe.lax, "ragged_dot", stale)
    got, _ = _expert_layer(uncut, 2, 0, 4, True, x + 0.0)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fault", ["softmax", "no_bias", "no_norm",
                                   "no_scale"])
def test_each_routing_option_changes_the_layer(fault, uncut):
    """The op's routing options against the reference's own equations with
    one of them taken away: every one shows."""
    x = np.random.default_rng(3).standard_normal((24, 32)).astype(np.float32)
    names = ["router_weight", "router_bias", "gate_weight", "down_weight",
             "up_weight"]
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    attrs = dict(num_experts=8, hidden_size=24, k=2, act_type="silu",
                 gated=True, no_bias=True, normalize=True,
                 score_func="sigmoid", select_bias=True, route_scale=2.826)

    def run(**change):
        a = dict(attrs, **change)
        operands = v if a["select_bias"] else v[:2] + v[3:]
        node = mx.sym.MoE(*operands, **a)
        exe = node.bind(mx.cpu(), {
            n: mx.nd.array(x if n == "data" else np.asarray(uncut["l1_" + n]))
            for n in node.list_arguments()}, grad_req="null")
        exe.forward(is_train=False)
        return exe.outputs[0].asnumpy()

    right = run()
    wrong = run(**{"softmax": dict(score_func="softmax"),
                   "no_bias": dict(select_bias=False),
                   "no_norm": dict(normalize=False),
                   "no_scale": dict(route_scale=1.0)}[fault])
    assert np.abs(right - wrong).max() > 1e-2 * np.abs(right).max()


# ----------------------------------------------------------------------
# the ring kernel on a ring shorter than the session
# ----------------------------------------------------------------------

RING = 256


@pytest.mark.parametrize("lengths", [(5, 127, 128, 255), (256, 300, 511),
                                     (1000, 0, 383)])
def test_the_kernel_on_a_wrapped_ring_matches_the_body(lengths):
    """The TPU's kernel in Pallas's interpreter against the `jax.numpy`
    body for a ring of 256 positions (two blocks of 128) and sessions
    before, at and past the wrap: the row is written at ``length mod 256``
    — in a block that is not the last one read once the ring is full —
    and a full ring is read whole."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.kv_ring_kernel import ring_attention

    rng = np.random.default_rng(sum(lengths))
    b, slots, h_q, h_kv, d = len(lengths), 5, 4, 2, 64

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, kn, vn = draw(b, h_q, d), draw(b, h_kv, d), draw(b, h_kv, d)
    kc, vc = draw(slots, h_kv, d, RING), draw(slots, h_kv, d, RING)
    slot = jnp.arange(b, dtype=jnp.int32) + 1
    length = jnp.asarray(lengths, jnp.int32)
    assert attention.decode_block(kc.shape, "tpu") == RING
    want = attention._ring_attention(q, kn, vn, kc, vc, slot, length,
                                     wraps=True)
    got = ring_attention(q, kn, vn, kc, vc, slot, length, block=128,
                         heads=2, interpret=True, wraps=True)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)
    # the new row lies at length mod 256 and nowhere else
    for i, n in enumerate(lengths):
        page = np.asarray(got[1][i + 1])
        np.testing.assert_array_equal(page[:, :, n % RING], np.asarray(kn[i]))
        others = np.delete(np.arange(RING), n % RING)
        np.testing.assert_array_equal(page[:, :, others],
                                      np.asarray(kc[i + 1])[:, :, others])


def test_the_window_masks_inside_a_sequence_and_a_long_prefill_writes_modulo():
    """`_sdp_attention` with a window of 3 against numpy, and
    `_kv_cache_write` of a 10-position block of true length 7 into a ring
    of 4: ring position r holds the newest p < 7 with p mod 4 == r."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    q, k, v = (rng.standard_normal((1, 6, 8)).astype(np.float32)
               for _ in range(3))
    got = np.asarray(attention.sdp_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), num_heads=2,
        window=3)[0])
    for h in range(2):
        qh, kh, vh = (x[0, :, 4 * h:4 * h + 4] for x in (q, k, v))
        for i in range(6):
            j = np.arange(max(0, i - 2), i + 1)   # itself and the 2 before
            s = qh[i] @ kh[j].T / 2.0
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vh[j]
            np.testing.assert_allclose(got[0, i, 4 * h:4 * h + 4], want,
                                       rtol=2e-5, atol=2e-6)
    block = jnp.asarray(rng.standard_normal((1, 2, 10, 4)), jnp.float32)
    ring = jnp.zeros((3, 2, 4, 4), jnp.float32)
    kc, vc = attention.kv_cache_write(ring, ring, block, 2 * block,
                                      jnp.asarray([1.0]), jnp.asarray([7.0]),
                                      window=4)
    for r, p in enumerate([4, 5, 6, 3]):
        np.testing.assert_array_equal(np.asarray(kc[1, :, :, r]),
                                      np.asarray(block[0, :, p]))
        np.testing.assert_array_equal(np.asarray(vc[1, :, :, r]),
                                      2 * np.asarray(block[0, :, p]))
    assert not np.asarray(kc[0]).any() and not np.asarray(kc[2]).any()


# ----------------------------------------------------------------------
# what a session holds, and what it counts
# ----------------------------------------------------------------------

def test_cache_spec_gives_every_ring_its_kinds_length():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(5, 64)
    assert list(spec) == [n % i for i in range(5)
                          for n in ("k_cache_%d", "v_cache_%d")]
    assert all(e.kind == "ring" for e in spec.values())
    lengths = [spec["k_cache_%d" % i].shape for i in range(5)]
    assert lengths == [(5, 2, 16, W), (5, 2, 16, W), (5, 2, 16, 64),
                       (5, 2, 16, W), (5, 2, 16, W)]
    # a session shorter than the window holds no more than its length
    assert lm.cache_spec(5, 6)["k_cache_0"].shape == (5, 2, 16, 6)
    # the server's admission and the census charge the bytes of THIS spec
    assert sum(e.nbytes for e in spec.values()) == 2 * 4 * 5 * 2 * 16 * (
        4 * W + 64)


def test_the_layer_kinds_declare_their_counters():
    lm = family.model(CONFIG)
    page = 2 * 4 * 2 * 16 * W               # one slot's K and V window page
    assert lm.call_counters(positions=32, platform="cpu") == {
        "attn.prefill_positions": 5 * 32, "attn.kernel_positions": 0,
        # the CPU's body computes ONE block a window layer, either mask
        "attn.band_blocks": 4, "attn.causal_blocks": 4,
        "kv.window_rows": 0, "kv.wrapped_rows": 0, "cache.window_bytes": 0,
        "moe.routed_pairs": 4 * 32 * 2}
    assert lm.call_counters(rows=3, lengths=[3, 8, 30], computed=4, pages=10,
                            max_len=64, platform="cpu") == {
        "attn.prefill_positions": 0, "attn.kernel_positions": 0,
        "attn.band_blocks": 0, "attn.causal_blocks": 0,
        "kv.window_rows": 4 * 3, "kv.wrapped_rows": 4 * 2,
        "cache.window_bytes": 4 * 10 * page, "moe.routed_pairs": 4 * 4 * 2}
    assert TransformerLM(vocab=8).call_counters(rows=4, lengths=[1] * 4) == {
        "attn.prefill_positions": 0, "attn.kernel_positions": 0}


def test_the_batcher_books_rings_of_two_lengths(held):
    """Two requests through `admit` / `decode_step`: the `kv.*` counters
    are means over the rings (8 of 8 positions, 2 of 64), a wrapped ring is
    read whole, the window counters and `moe.routed_pairs` move as the
    kinds declare, and `moe.pairs` counts the pairs on held experts."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("kv.reserved_positions", "kv.used_positions",
             "kv.page_positions", "kv.skipped_positions", "kv.window_rows",
             "kv.wrapped_rows", "cache.window_bytes", "cache.reserved_bytes",
             "moe.pairs", "moe.routed_pairs", "serving.decode.dispatches")
    session = _session(held, max_sessions=2)
    try:
        before = {n: telemetry.counter_value(n) for n in names}
        reqs = [GenerateRequest("lm", TOKENS[:n], 30.0, 6) for n in (5, 20)]
        assert session.admit(reqs) == []
        while session.active():
            session.decode_step()
        moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    finally:
        session.close()
        telemetry.set_enabled(was)
    for r in reqs:
        assert len(r.future.result(timeout=5).tokens) == 6
    # five rows a session: the first session's first rides the second
    # prompt's mixed step, so its last leaves the second's alone
    assert moved["serving.decode.dispatches"] == 6
    lengths = [p + s for p in (5, 20) for s in range(5)]
    mean = lambda per_ring: sum(per_ring) / 10.0   # noqa: E731 (8 + 2 rings)
    assert moved["kv.used_positions"] == pytest.approx(sum(
        mean([min(n, W)] * 8 + [n] * 2) for n in lengths))
    assert moved["kv.page_positions"] == pytest.approx(
        len(lengths) * mean([W] * 8 + [64] * 2))
    assert moved["kv.skipped_positions"] == 0   # the CPU reads whole pages
    assert moved["kv.window_rows"] == len(lengths) * 4
    assert moved["kv.wrapped_rows"] == 4 * sum(n >= W for n in lengths)
    assert 0 < moved["cache.window_bytes"] < moved["cache.reserved_bytes"]
    assert moved["cache.window_bytes"] * (4 * W + 64) == (
        moved["cache.reserved_bytes"] * 4 * W)
    # mixed steps of buckets 8 and 32 with their two rows each, then four
    # 2-row steps and a 1-row one
    assert moved["moe.routed_pairs"] == 4 * 2 * (8 + 2 + 32 + 2 + 4 * 2 + 1)
    assert 0 < moved["moe.pairs"] < moved["moe.routed_pairs"]
