"""Qwen3-Next's block through `TransformerLM` and `GenerativeSession`:
three Gated DeltaNet layers whose q and k have HALF as many heads as
their values (4 under 8, each q/k head read by two value heads) to one
gated attention of 4 query heads of 32 over 2 K/V heads with the first 8
channels of each head rotated, per-head QK-norm, input norms only, and in
every layer 4 of 16 softmax-routed experts, renormalised, beside a shared
expert with a sigmoid gate of its own, of which this model holds a quarter
— against the plain reference of the benchmark
(benchmarks/reference/qwen3_next.py: float32 `jax.numpy` at "highest", the
delta rule position by position, independent of `mxnet_tpu`).

Tiny widths (4 layers, hidden 64), both sides float32 on the CPU: errors
are float32 rounding (measured 5e-6 of the largest logit); the bound 1e-4
is far above that and a fortieth of what one bfloat16 pass leaves.  The
file costs about 70 s.
"""
import json
import os
import sys

import numpy as np
import pytest
from test_gdn_kernel import _tpu_kernel_interpreted

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import attention, gdn
from mxnet_tpu.serving import GenerateRequest, GenerativeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import qwen3_next as family  # noqa: E402
from benchmarks.reference import qwen3_next as reference  # noqa: E402

CONFIG = {"vocab_size": 67, "hidden_size": 64, "intermediate_size": 96,
          "head_dim": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
          "partial_rotary_factor": 0.25, "rope_theta": 10000000,
          "linear_num_key_heads": 4, "linear_num_value_heads": 8,
          "linear_key_head_dim": 16, "linear_value_head_dim": 16,
          "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
          "num_hidden_layers": 4, "decoder_sparse_step": 1,
          "mlp_only_layers": [], "moe_intermediate_size": 32,
          "shared_expert_intermediate_size": 32, "num_experts": 4,
          "router_experts": 16, "held_experts": [0, 4],
          "num_experts_per_tok": 4, "norm_topk_prob": True,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "max_position_embeddings": 128, "param_dtype": "float32"}
UNCUT = dict(CONFIG, num_experts=16, held_experts=[0, 16])
RTOL = 1e-4  # of the largest |logit|; see the module docstring
SMALL = ("_gamma", "_A_log", "_dt_bias", "_conv_weight")


def _params(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x10 makes every part of the block matter, and spreads the
    # router's logits over a few units
    p = family.make_params(config, seed, jax.devices("cpu")[0])
    return {k: v if k.endswith(SMALL) else 10.0 * v for k, v in p.items()}


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
    kw = dict(dict(max_sessions=3, max_len=128, max_decode_tokens=64,
                   seq_buckets=[8, 32]), **kw)
    return GenerativeSession("lm", family.model(config), held, **kw)


TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 67, 56)]


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "another_share", "uncut"])
def test_score_symbol_matches_the_reference(which, uncut):
    """The full-sequence graph (the delta rule in chunks of 64: one short
    chunk here), for the share the cell holds, another chip's share, and
    the uncut model."""
    config, p = {"share": (CONFIG, _share(uncut, 0, 4)),
                 "another_share": (dict(CONFIG, held_experts=[8, 4]),
                                   _share(uncut, 8, 4)),
                 "uncut": (UNCUT, uncut)}[which]
    got = _score(family.model(config), _hold(p), TOKENS)
    _close(got, reference.logits(p, config, TOKENS))


@pytest.mark.parametrize("prompt,bucket", [(5, 8), (20, 32), (26, 32)])
def test_prefill_then_decode_through_ring_and_state_matches_the_reference(
        prompt, bucket, params, held):
    """Prefill and then every decode step to position 56 — 51, 36 and 30
    steps — against ONE full forward of the reference: the chunked rule's
    final state and conv window handed to the state step, the attention
    layer's ring written by the prefill and then a row a step, every
    row's experts routed 16 wide with four held."""
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
    assert len(got) - 1 >= 30
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


# ----------------------------------------------------------------------
# one chip's share of an expert layer, and the shared expert's gate
# ----------------------------------------------------------------------

def _expert_layer(p, i, first, count, shared, x, **change):
    """Layer i's `mx.sym.MoE` node alone on `x (T, d)`, holding experts
    `first` .. `first + count`, with or without the gated shared expert."""
    names = ["router_weight", "gate_weight", "down_weight", "up_weight"]
    if shared:
        names += ["shared_gate_weight", "shared_down_weight",
                  "shared_up_weight"]
        if change.get("shared_gate", True):
            names.append("shared_score_weight")
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    attrs = dict(num_experts=16, hidden_size=32, k=4, act_type="silu",
                 gated=True, no_bias=True, normalize=True, held_first=first,
                 held_count=count, shared_size=32 if shared else 0,
                 shared_gate=bool(shared), return_load=True)
    node = mx.sym.MoE(*v, **dict(attrs, **change))
    values = {n: np.asarray(p["l%d_%s" % (i, n)]) for n in names}
    for n in ("gate_weight", "down_weight", "up_weight"):
        values[n] = values[n][first:first + count]
    exe = node.bind(mx.cpu(), dict({"data": mx.nd.array(x)}, **{
        n: mx.nd.array(a) for n, a in values.items()}), grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


def _uncut_layer(uncut, i, x):
    import jax

    layer = lambda n: uncut["l%d_%s" % (i, n)]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.expert_layer(
            x, layer("router_weight"), layer("gate_weight"),
            layer("up_weight"), layer("down_weight"),
            (layer("shared_gate_weight"), layer("shared_up_weight"),
             layer("shared_down_weight")), layer("shared_score_weight"),
            4, True, 0)[0])


def test_the_four_quarters_and_the_gated_shared_expert_once_make_the_layer(
        uncut):
    """THE SHARE TEST: the outputs of one expert layer held as experts
    0-3, 4-7, 8-11 and 12-15 (the router 16 wide, 4 a token, renormalised
    over the four, on all), the gated shared expert counted once, add up
    to what the uncut reference gives for the whole layer; each quarter's
    load counts its own experts' pairs, which together are every pair."""
    x = np.random.default_rng(2).standard_normal((24, 64)).astype(np.float32)
    want = _uncut_layer(uncut, 1, x)
    parts = [_expert_layer(uncut, 1, first, 4, first == 0, x)
             for first in (0, 4, 8, 12)]
    _close(sum(out for out, _ in parts), want, 1e-5)
    assert all(load.shape == (4,) for _, load in parts)
    assert sum(load.sum() for _, load in parts) == 24 * 4
    assert all(load.sum() > 0 for _, load in parts)
    # no quarter is the layer: the other chips' terms are LEFT OUT
    assert np.abs(parts[0][0] - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("fault", ["no_shared_gate", "no_norm", "sigmoid"])
def test_each_option_of_this_expert_layer_changes_it(fault, uncut):
    x = np.random.default_rng(3).standard_normal((24, 64)).astype(np.float32)
    right, _ = _expert_layer(uncut, 0, 0, 16, True, x)
    _close(right, _uncut_layer(uncut, 0, x), 1e-5)
    wrong, _ = _expert_layer(uncut, 0, 0, 16, True, x, **{
        "no_shared_gate": dict(shared_gate=False),
        "no_norm": dict(normalize=False),
        "sigmoid": dict(score_func="sigmoid")}[fault])
    assert np.abs(right - wrong).max() > 1e-2 * np.abs(right).max()


def test_a_shared_gate_needs_a_shared_expert():
    with pytest.raises(ValueError, match="shared_gate"):
        TransformerLM(vocab=8, num_experts=4, experts_per_token=2,
                      shared_gate=True)


# ----------------------------------------------------------------------
# rotary over part of a head
# ----------------------------------------------------------------------

def test_rotary_dim_turns_the_leading_channels_and_passes_the_rest():
    """With `rotary_dim` 8 of a head of 32: channels 0-7 of each head are
    what the whole-head rotary makes of a head of 8, channels 8-31 come
    back as they went in; `_rotary_at` the same at each row's own
    position; a `rotary_dim` of the whole head is the whole-head rotary."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 6, 4 * 32)), jnp.float32)
    heads = x.reshape(2, 6, 4, 32)
    got = attention.rotary(x, num_heads=4, theta=1e7,
                           rotary_dim=8).reshape(2, 6, 4, 32)
    want = attention.rotary(heads[..., :8].reshape(2, 6, 32), num_heads=4,
                            theta=1e7).reshape(2, 6, 4, 8)
    np.testing.assert_array_equal(np.asarray(got[..., :8]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(heads[..., 8:]))
    assert np.abs(np.asarray(got[:, 1:, :, :8] - heads[:, 1:, :, :8])).max() > 0.1
    index = jnp.asarray([3.0, 5.0])
    at = attention.rotary_at(x[:, :1], index, num_heads=4, theta=1e7,
                             rotary_dim=8).reshape(2, 1, 4, 32)
    np.testing.assert_allclose(np.asarray(at[0, 0]), np.asarray(
        attention.rotary(jnp.tile(x[:1, :1], (1, 6, 1)), num_heads=4,
                         theta=1e7, rotary_dim=8).reshape(6, 4, 32)[3]),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(at[..., 8:]),
                                  np.asarray(heads[:, :1, :, 8:]))
    np.testing.assert_array_equal(
        np.asarray(attention.rotary(x, num_heads=4, rotary_dim=32)),
        np.asarray(attention.rotary(x, num_heads=4)))
    with pytest.raises(ValueError, match="rotary_dim"):
        TransformerLM(vocab=8, positions="rotary", rotary_dim=7)


# ----------------------------------------------------------------------
# the two TPU kernels at this model's shapes, in Pallas's interpreter
# ----------------------------------------------------------------------

def _gdn_prefill(hk, h, dk, dv, bucket, length, seed):
    import jax.numpy as jnp

    conv_dim = gdn.conv_channels(h, dk, dv, hk)
    rng = np.random.RandomState(seed)
    data = rng.randn(1, bucket, conv_dim + h * dv + 2 * h).astype(np.float32)
    attrs = dict(num_heads=h, num_key_heads=hk, key_dim=dk, value_dim=dv,
                 conv_kernel=4, chunk_size=16, neg_eigval=False, eps=1e-6)
    out = gdn.gdn_prefill(
        jnp.asarray(data), jnp.asarray(0.5 * rng.randn(4, conv_dim), jnp.float32),
        jnp.asarray(rng.randn(h), jnp.float32),
        jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32),
        jnp.asarray(1 + 0.1 * rng.randn(dv), jnp.float32),
        jnp.zeros((3, 3, conv_dim), jnp.float32),
        jnp.zeros((3, dk, h * dv), jnp.float32),
        jnp.asarray([1.0]), jnp.asarray([float(length)]), **attrs)
    return [np.asarray(o) for o in out]


def test_the_delta_rule_kernel_at_two_key_heads_under_four_value_heads():
    """`_gdn_prefill` with 2 q/k heads under 4 value heads, three chunks
    of 16: the TPU's kernel (which sees four heads, q and k repeated after
    the norm) in Pallas's interpreter against the `jax.numpy` body, and
    both against the same rule with q and k repeated BEFORE the op — four
    key heads whose projections and conv taps are the two's, doubled."""
    import jax.numpy as jnp

    want = _gdn_prefill(2, 4, 16, 32, 48, 41, seed=7)
    with _tpu_kernel_interpreted() as calls:
        got = _gdn_prefill(2, 4, 16, 32, 48, 41, seed=7)
    assert len(calls) == 1
    _close(got[0], want[0], 2e-5)
    np.testing.assert_array_equal(got[1], want[1])   # the window: copied rows
    _close(got[2], want[2], 2e-5)
    assert gdn.chunk_heads((1, 48, 4, 16), 32, 16, "tpu") == 4
    # the same by hand: value head n reads q/k head n // 2
    rng = np.random.RandomState(7)
    data = rng.randn(1, 48, 2 * 2 * 16 + 4 * 32 + 4 * 32 + 8).astype(np.float32)
    conv = (0.5 * rng.randn(4, 2 * 2 * 16 + 4 * 32)).astype(np.float32)
    wide = lambda x: np.concatenate(  # noqa: E731: [q | k | rest], q and k doubled
        [np.repeat(x[..., :32].reshape(x.shape[:-1] + (2, 16)), 2,
                   axis=-2).reshape(x.shape[:-1] + (64,)),
         np.repeat(x[..., 32:64].reshape(x.shape[:-1] + (2, 16)), 2,
                   axis=-2).reshape(x.shape[:-1] + (64,)), x[..., 64:]],
        axis=-1)
    rest = [jnp.asarray(rng.randn(4), jnp.float32),
            jnp.asarray(np.log(rng.uniform(1, 16, 4)), jnp.float32),
            jnp.asarray(1 + 0.1 * rng.randn(32), jnp.float32)]
    four = gdn.gdn_scan(jnp.asarray(wide(data)), jnp.asarray(wide(conv)),
                        *rest, num_heads=4, key_dim=16, value_dim=32,
                        chunk_size=16, neg_eigval=False)
    two = gdn.gdn_scan(jnp.asarray(data), jnp.asarray(conv), *rest,
                       num_heads=4, num_key_heads=2, key_dim=16,
                       value_dim=32, chunk_size=16, neg_eigval=False)
    _close(two, four, 1e-6)


@pytest.mark.parametrize("lengths", [(5, 127, 128), (255, 300, 511)])
def test_the_ring_kernel_at_a_head_of_256_matches_the_body(lengths):
    """The decode-attention kernel in Pallas's interpreter against the
    `jax.numpy` body for heads of 256 — each over two tiles of 128 lines —
    4 query heads on 2 K/V heads, a ring of 512 in blocks of 128."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.kv_ring_kernel import ring_attention

    rng = np.random.default_rng(sum(lengths))
    b, slots, h_q, h_kv, d, ring = len(lengths), 5, 4, 2, 256, 512

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, kn, vn = draw(b, h_q, d), draw(b, h_kv, d), draw(b, h_kv, d)
    kc, vc = draw(slots, h_kv, d, ring), draw(slots, h_kv, d, ring)
    slot = jnp.arange(b, dtype=jnp.int32) + 1
    length = jnp.asarray(lengths, jnp.int32)
    assert attention.decode_heads(kc.shape) == 2
    assert attention.decode_block(kc.shape, "tpu") == ring
    assert attention.decode_heads((17, 2, 256, 4096)) == 2
    assert attention.decode_block((17, 2, 256, 4096), "tpu") == 512
    assert attention.decode_heads((9, 2, 192, 512)) is None   # 1.5 tiles
    want = attention._ring_attention(q, kn, vn, kc, vc, slot, length)
    got = ring_attention(q, kn, vn, kc, vc, slot, length, block=128,
                         heads=2, interpret=True)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(np.asarray(got[1][i + 1])[:, :, n],
                                      np.asarray(kn[i]))


# ----------------------------------------------------------------------
# what a session holds and counts; what the other models must not notice
# ----------------------------------------------------------------------

def test_cache_spec_and_counters_take_both_head_counts():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(5, 128)
    conv_dim = 2 * 4 * 16 + 8 * 16
    assert list(spec) == ["conv_state_0", "gdn_state_0", "conv_state_1",
                          "gdn_state_1", "conv_state_2", "gdn_state_2",
                          "k_cache_3", "v_cache_3"]
    assert spec["conv_state_0"].shape == (5, 3, conv_dim)
    assert spec["gdn_state_0"].shape == (5, 16, 8 * 16)
    assert spec["k_cache_3"].shape == (5, 2, 32, 128)
    page = 4 * (3 * conv_dim + 16 * 8 * 16)
    assert lm.call_counters(positions=32, platform="cpu") == {
        "attn.prefill_positions": 32, "attn.kernel_positions": 0,
        "gdn.scan_positions": 3 * 32, "gdn.kernel_positions": 0,
        "gdn.state_bytes": 0, "gdn.step_kernel_bytes": 0,
        "moe.routed_pairs": 4 * 32 * 4}
    assert lm.call_counters(rows=3, lengths=[3, 8, 30], computed=4, pages=10,
                            max_len=128, platform="cpu") == {
        "attn.prefill_positions": 0, "attn.kernel_positions": 0,
        "gdn.scan_positions": 0, "gdn.kernel_positions": 0,
        "gdn.state_bytes": 3 * 2 * 3 * page, "gdn.step_kernel_bytes": 0,
        "moe.routed_pairs": 4 * 4 * 4}
    # lowered for the TPU, the step kernel moves all of them: eight heads
    # of 16 values are one lane tile
    assert lm.call_counters(rows=3, platform="tpu")[
        "gdn.step_kernel_bytes"] == 3 * 2 * 3 * page
    # at the published widths a 2,048 bucket runs through the kernel
    real = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json")))
    counted = family.model(real).call_counters(positions=2048, platform="tpu")
    assert counted["gdn.kernel_positions"] == counted["gdn.scan_positions"] \
        == 3 * 2048
    stepped = family.model(real).call_counters(rows=16, platform="tpu")
    assert stepped["gdn.step_kernel_bytes"] == stepped["gdn.state_bytes"] > 0


def test_the_batcher_books_recurrent_state_experts_and_the_ring(held):
    """Two requests through `admit` / `decode_step`: a recurrent mixer and
    a routed FFN in one serving graph — `moe.*` beside `gdn.*`, `cache.*`
    and `kv.*`; `kv.kernel_positions` stays 0 off the TPU."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("kv.page_positions", "kv.kernel_positions", "gdn.scan_positions",
             "gdn.state_bytes", "cache.reserved_bytes", "cache.state_bytes",
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
    assert moved["kv.page_positions"] == 5 * 2 * 128
    assert moved["kv.kernel_positions"] == 0
    assert moved["gdn.scan_positions"] == 3 * (8 + 32)
    assert 0 < moved["cache.state_bytes"] < moved["cache.reserved_bytes"]
    assert moved["moe.routed_pairs"] == 4 * 4 * (8 + 2 + 32 + 2 + 4 * 2 + 1)
    assert 0 < moved["moe.pairs"] < moved["moe.routed_pairs"]
