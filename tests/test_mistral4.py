"""Mistral-Small-4's block through `TransformerLM` and `GenerativeSession`:
multi-head latent attention — a low-rank query with a norm between its two
projections, ONE cached row ``[c | k_r]`` a position for all heads, the
per-head up-projection that the prefill applies and the decode step
absorbs, YaRN's blended frequencies on the decoupled rotary part, a
softmax scale that is not ``1/sqrt(d_head)``, the query's position scale —
and in every layer 2 of 16 softmax-routed experts, renormalised, beside an
ungated shared expert, of which this model holds a quarter — against the
plain reference of the benchmark (benchmarks/reference/mistral4.py:
float32 `jax.numpy` at "highest", every head's K and V made from the
latent rows, the checkpoint's interleaved layout, independent of
`mxnet_tpu`).

Tiny widths (2 layers, hidden 64, 4 heads of 8 + 8 over a latent of 24 + 8;
YaRN over 16 trained positions so that every test runs past them), both
sides float32 on the CPU: errors are float32 rounding (measured 1e-6 of
the largest logit); the bound 1e-4 is far above that and far below what
one bfloat16 pass leaves.  The file costs about 60 s.
"""
import json
import math
import os
import sys
from unittest import mock

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import attention, latent
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serving import GenerativeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import mistral4 as family  # noqa: E402
from benchmarks.reference import mistral4 as reference  # noqa: E402

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}


def _sigma(head, rope):
    return head ** -0.5 * (0.1 * rope["mscale_all_dim"]
                           * math.log(rope["factor"]) + 1) ** 2


CONFIG = {"vocab_size": 67, "hidden_size": 64, "intermediate_size": 96,
          "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
          "qk_head_dim": 16, "q_lora_rank": 32, "kv_lora_rank": 24,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "rope_interleave": True, "rope_parameters": ROPE,
          "num_hidden_layers": 2, "first_k_dense_replace": 0,
          "moe_intermediate_size": 32, "n_routed_experts": 4,
          "router_experts": 16, "held_experts": [0, 4],
          "n_shared_experts": 1, "num_experts_per_tok": 2,
          "norm_topk_prob": True, "routed_scaling_factor": 1, "n_group": 1,
          "topk_group": 1, "rms_norm_eps": 1e-6,
          "tie_word_embeddings": False, "max_position_embeddings": 512,
          "param_dtype": "float32",
          "assumed": {"softmax_scale": {"value": _sigma(16, ROPE)},
                      "router": {"scoring_func": "softmax",
                                 "selection_bias": False},
                      "query_scale": {"applied": True}}}
UNCUT = dict(CONFIG, n_routed_experts=16, held_experts=[0, 16])
RTOL = 1e-4  # of the largest |logit|; see the module docstring
REAL = os.path.join(ROOT, "benchmarks", "configs",
                    "mistral-small-4-119b.json")


def _params(config, seed=5):
    import jax

    # the init's 0.02 makes every projection's output small against the
    # gains; x10 makes every part of the block matter, and spreads the
    # router's logits over a few units
    p = family.make_params(config, seed, jax.devices("cpu")[0])
    return {k: v if k.endswith("_gamma") else 10.0 * v for k, v in p.items()}


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


def _session(held, config=CONFIG, lm=None, **kw):
    kw = dict(dict(max_sessions=4, max_len=128, max_decode_tokens=64,
                   seq_buckets=[8, 32]), **kw)
    return GenerativeSession("lm", lm or family.model(config), held, **kw)


def _want(p, config, tokens):
    return np.asarray(reference.logits(family.checkpoint_layout(p, config),
                                       config, tokens))


TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 67, 56)]


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "another_share", "uncut"])
def test_score_symbol_matches_the_reference(which, uncut):
    """The full-sequence graph (the up-projected form), 56 positions — 40
    past the 16 YaRN was trained on, so the query's position scale is 1.11
    and more there — for the share the cell holds, another chip's share,
    and the uncut model."""
    config, p = {"share": (CONFIG, _share(uncut, 0, 4)),
                 "another_share": (dict(CONFIG, held_experts=[8, 4]),
                                   _share(uncut, 8, 4)),
                 "uncut": (UNCUT, uncut)}[which]
    got = _score(family.model(config), _hold(p), TOKENS)
    _close(got, _want(p, config, TOKENS))


@pytest.mark.parametrize("prompt,bucket", [(5, 8), (20, 32), (32, 32)])
def test_prefill_then_decode_through_the_latent_ring_matches_the_reference(
        prompt, bucket, params, held):
    """Prefill (the up-projected form, its 32-wide rows written to the one
    ring) and then every decode step to position 56 (the absorbed form
    over the ring) against ONE full forward of the reference: from both
    buckets, a bucket's pad behind the prompt and a prompt that fills its
    bucket to the edge."""
    session = _session(held)
    try:
        toks, got = TOKENS[:prompt], []
        exe, fn = session._program(session._prefill_pred, 1, bucket, True)
        data = np.zeros((1, bucket), np.float32)
        data[0, :prompt] = toks
        at = np.full((1,), 2, np.float32)
        got.append(session._run(exe, fn, data, at,
                                np.full((1,), prompt, np.float32))[0])
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        for t in range(prompt, len(TOKENS)):
            got.append(session._run(
                exe, fn, np.asarray([[TOKENS[t]]], np.float32), at,
                np.full((1,), t, np.float32))[0])
    finally:
        session.close()
    _close(np.asarray(got), _want(params, CONFIG, TOKENS)[prompt - 1:])


def test_a_padded_decode_batch_of_three_sessions_matches_the_reference(
        params, held):
    """Three sessions of different lengths in slots 3, 0, 2 through the
    FOUR-row decode program, the fourth row the pad (the scratch slot at
    length 0): every row's logits of every step are its own sequence's."""
    prompts = [TOKENS[:5], TOKENS[10:30], TOKENS[3:11]]
    slots, steps = [3, 0, 2], 12
    session = _session(held)
    try:
        seqs, got = [list(p) for p in prompts], [[] for _ in prompts]
        for r, (prompt, bucket) in enumerate(zip(prompts, (8, 32, 8))):
            exe, fn = session._program(session._prefill_pred, 1, bucket, True)
            data = np.zeros((1, bucket), np.float32)
            data[0, :len(prompt)] = prompt
            got[r].append(session._run(
                exe, fn, data, np.full((1,), slots[r], np.float32),
                np.full((1,), len(prompt), np.float32))[0])
        exe, fn = session._program(session._decode_pred, 4, 1, False)
        for _ in range(steps):
            data = np.zeros((4, 1), np.float32)
            slot = np.full((4,), session._slots, np.float32)   # scratch
            length = np.zeros((4,), np.float32)
            for r, seq in enumerate(seqs):
                length[r], slot[r] = len(seq), slots[r]
                seq.append(int(np.argmax(got[r][-1])))
                data[r, 0] = seq[-1]
            out = session._run(exe, fn, data, slot, length)
            for r in range(3):
                got[r].append(out[r])
    finally:
        session.close()
    for prompt, seq, mine in zip(prompts, seqs, got):
        _close(np.asarray(mine),
               _want(params, CONFIG, seq)[len(prompt) - 1:])


def test_the_absorbed_step_equals_the_up_projected_step_on_the_same_cache():
    """`_latent_cached_attention` over a ring filled with T rows gives
    the last row of `_latent_attention` over the same T + 1 rows: the two
    forms are one function, re-associated."""
    rng = np.random.default_rng(7)
    h, nope, rope, value, rank, t = 4, 8, 8, 16, 24, 37
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q_nope, q_rope = f(1, t + 1, h * nope), f(1, t + 1, h * rope)
    rows, kvb = f(1, t + 1, rank + rope), f(h * (nope + value), rank)
    attrs = dict(num_heads=h, rope_dim=rope, value_dim=value, scale=0.3,
                 query_scale=(0.1, 16))
    full = np.asarray(latent.latent_attention(q_nope, q_rope, rows, kvb,
                                              **attrs))
    ring = np.zeros((3, 1, rank + rope, 128), np.float32)
    ring[1, 0, :, :t] = rows[0, :t].T
    step, ring2 = latent.latent_cached_attention(
        q_nope[:, t:], q_rope[:, t:], rows[:, t:], kvb, ring,
        np.asarray([1.0], np.float32), np.asarray([float(t)], np.float32),
        **attrs)
    _close(np.asarray(step)[0, 0], full[0, t], 1e-5)
    assert np.array_equal(np.asarray(ring2)[1, 0, :, t], rows[0, t])
    assert np.array_equal(np.asarray(ring2)[1, 0, :, :t], ring[1, 0, :, :t])


# ----------------------------------------------------------------------
# the TPU's kernel, interpreted, against the jax.numpy body
# ----------------------------------------------------------------------

@pytest.mark.parametrize("lengths", [(0, 127, 128), (129, 255, 256),
                                     (300, 383, 5)])
def test_the_latent_kernel_interpreted_matches_the_body(lengths):
    """Blocks of 128 positions of a ring of 384: `length` on a block's
    last position, on the next one's first, inside one and at the ring's
    end; rows in slots out of order; the ring comes back with each new
    row at its position and nothing else touched."""
    from mxnet_tpu.ops.latent_ring_kernel import latent_ring_attention

    rng = np.random.default_rng(sum(lengths))
    heads, width, rank, slots = 8, 32, 24, 5
    q = rng.standard_normal((3, heads, width)).astype(np.float32)
    new = rng.standard_normal((3, width)).astype(np.float32)
    ring = rng.standard_normal((slots, 1, width, 384)).astype(np.float32)
    slot = np.asarray([4, 0, 2], np.int32)
    length = np.asarray(lengths, np.int32)
    want_ctx, want_ring = latent._latent_ring_attention(
        q, new, ring, slot, length, rank=rank, scale=0.3)
    got_ctx, got_ring = latent_ring_attention(
        q, new, ring, slot, length, rank=rank, block=128, scale=0.3,
        interpret=True)
    _close(got_ctx, want_ctx, 1e-5)
    assert np.array_equal(np.asarray(got_ring), np.asarray(want_ring))


def test_the_decode_op_with_the_interpreted_kernel_matches_the_body():
    """`_latent_cached_attention` with its page read handed to the
    kernel's own code (interpreted: on the CPU `lax.platform_dependent`
    takes the body's branch, so the test puts the kernel in the op's
    place as a TPU lowering would) agrees with the op through the body:
    the absorbed products around the kernel, lengths across a block's
    edge and at the ring's end."""
    from mxnet_tpu.ops.latent_ring_kernel import latent_ring_attention

    rng = np.random.default_rng(3)
    h, nope, rope, value, rank = 4, 8, 8, 16, 24
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    args = (f(2, 1, h * nope), f(2, 1, h * rope), f(2, 1, rank + rope),
            f(h * (nope + value), rank), f(3, 1, rank + rope, 256),
            np.asarray([2.0, 0.0], np.float32),
            np.asarray([130.0, 255.0], np.float32))
    attrs = dict(num_heads=h, rope_dim=rope, value_dim=value, scale=0.3)
    want = latent.latent_cached_attention(*args, **attrs)

    def kernel(q, new, cache, slot_i, len_i, *, rank, scale, block,
               interpret):
        return latent_ring_attention(q, new, cache, slot_i, len_i,
                                     rank=rank, block=128, scale=scale,
                                     interpret=True)

    with mock.patch.object(latent, "_latent_decode", kernel):
        got = latent.latent_cached_attention(*args, **attrs)
    _close(got[0], want[0], 1e-5)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_decode_block_answers_for_the_latent_ring():
    """A ring of one 320-wide row a position has no block as a per-head
    ring (320 neither divides 128 nor is a multiple of it) and, as a
    latent ring, the largest multiple of 128 that divides it within 1 MiB:
    768 of 6,144, 512 of 4,096."""
    ring = (17, 1, 320, 6144)
    assert attention.decode_heads(ring) is None
    assert attention.decode_block(ring, "tpu") is None
    assert attention.decode_heads(ring, latent=True) == 1
    assert attention.decode_block(ring, "tpu", latent=True) == 768
    assert attention.decode_block((17, 1, 320, 4096), "tpu",
                                  latent=True) == 512
    assert attention.decode_block(ring, "cpu", latent=True) is None
    assert attention.decode_block((17, 1, 320, 1000), "tpu",
                                  latent=True) is None
    # per-head rings are answered as they were
    assert attention.decode_block((9, 16, 128, 768), "tpu") == 128
    assert attention.decode_heads((9, 30, 128, 2304)) == 15


# ----------------------------------------------------------------------
# rotary: YaRN's frequencies, the layout of the pairs, the query's scale
# ----------------------------------------------------------------------

def test_yarns_frequencies_are_the_closed_form():
    """The published sizes: 32 pairs of 64 channels, factor 128 over 8,192
    positions: lo = 12, hi = 25; pairs 0-12 turn at the base frequency,
    pairs 25-31 at 1/128 of it, pair j between at the blend ``((j - 12) /
    13) / 128 + (1 - (j - 12) / 13)`` of it; the program's and the
    reference's agree."""
    with open(REAL) as f:
        rope = json.load(f)["rope_parameters"]
    freqs, factor, (lo, hi) = reference.yarn_frequencies(rope, 64)
    assert (lo, hi) == (12, 25) and factor == 1.0
    base = 10000.0 ** (-np.arange(32) / 32.0)
    want = base.copy()
    want[25:] /= 128
    for j in range(13, 25):
        ramp = (j - 12) / 13.0
        want[j] = ramp * base[j] / 128 + (1 - ramp) * base[j]
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=2e-6)
    mine = attention._yarn_inv_freq(32, 10000.0, (128, 8192, 32, 1))
    np.testing.assert_allclose(np.asarray(mine), want, rtol=2e-6)
    assert want[12] == base[12] and want[25] == base[25] / 128
    # without `yarn` the op is the plain rotary it was
    x = np.random.default_rng(0).standard_normal((1, 5, 16)).astype("f")
    plain = np.asarray(attention.rotary(x, num_heads=2))
    assert not np.allclose(
        plain, np.asarray(attention.rotary(x, num_heads=2,
                                           yarn=(8, 16, 32, 1))))


def test_interleaved_pairs_are_the_de_interleaved_layout():
    """`layout_rows`: the program's row of `W_qb` / `W_kva` and the
    checkpoint's row it holds — q by kind and the pairs de-interleaved —
    and the rotation of the one is the rotation of the other: a rotary
    part turned by the program's rotate-half, taken back to the
    checkpoint's order, is the reference's interleaved rotation."""
    qb, kva = family.layout_rows(CONFIG)
    assert sorted(qb) == list(range(4 * 16)) and sorted(kva) == list(range(32))
    # head 1's q_nope channel 3 and its rotary pair 2: first and second
    assert qb[1 * 8 + 3] == 1 * 16 + 3
    assert qb[4 * 8 + 1 * 8 + 2] == 1 * 16 + 8 + 4
    assert qb[4 * 8 + 1 * 8 + 4 + 2] == 1 * 16 + 8 + 5
    assert list(kva[:24]) == list(range(24))
    assert list(kva[24:]) == [24, 26, 28, 30, 25, 27, 29, 31]
    x = np.random.default_rng(1).standard_normal((1, 40, 8)).astype("f")
    freqs, factor, _ = reference.yarn_frequencies(ROPE, 8)
    want = np.asarray(reference._rotary(x[0], freqs, factor))
    order = kva[24:] - 24
    got = np.asarray(attention.rotary(x[..., order], num_heads=1,
                                      yarn=(8, 16, 32, 1)))
    np.testing.assert_allclose(got[0], want[:, order], atol=1e-5)


def test_unpermuted_rows_are_another_model(params, held):
    """The program handed the checkpoint's layout as it is rotates the
    wrong pairs: its logits leave the reference's."""
    wrong = _hold(family.checkpoint_layout(params, CONFIG))
    got = _score(family.model(CONFIG), wrong, TOKENS)
    want = _want(params, CONFIG, TOKENS)
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_querys_position_scale_is_one_below_the_trained_length():
    """``1 + 0.1 ln(1 + floor(p / 8192))``: exactly 1 up to 8,191, 1.069
    from 8,192, 1.110 from 16,384 — in the op and in the reference; a
    model whose spec has no `query_scale` carries no such attribute."""
    pos = np.asarray([0, 100, 8191, 8192, 16383, 16384])
    got = np.asarray(latent._query_factor(pos, (0.1, 8192)))
    want = 1 + 0.1 * np.log(1 + pos // 8192)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (got[:3] == 1.0).all() and got[3] > 1.069
    np.testing.assert_allclose(
        np.asarray(reference.query_factor(pos, 0.1, 8192.0)), want, rtol=1e-6)
    config = dict(CONFIG, assumed=dict(CONFIG["assumed"],
                                       query_scale={"applied": False}))
    assert "query_scale" not in family.model(config).decode_symbol().tojson()
    assert "query_scale" in family.model(CONFIG).decode_symbol().tojson()


# ----------------------------------------------------------------------
# what a session keeps, and what the tenant charges and counts
# ----------------------------------------------------------------------

def test_cache_spec_has_one_latent_entry_a_layer():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(5, 128)
    assert list(spec) == ["latent_cache_0", "latent_cache_1"]
    assert all(e == ("latent", (5, 1, 32, 128)) for e in spec.values())
    with open(REAL) as f:
        real = family.model(json.load(f))
    spec = real.cache_spec(17, 6144)
    assert [e.shape for e in spec.values()] == [(17, 1, 320, 6144)] * 4
    # 1,280 bytes a position a layer, where per-head K and V rings of 32
    # heads of 128 would be 32,768
    assert {e.nbytes // (17 * 6144) for e in spec.values()} == {1280}
    assert 2 * 4 * 32 * 128 == 32768
    assert real.call_counters(positions=2048, platform="tpu") == {
        "attn.prefill_positions": 4 * 2048, "attn.kernel_positions": 4 * 2048,
        "mla.layer_steps": 0, "mla.kernel_steps": 0, "mla.ring_bytes": 0,
        "cache.latent_bytes": 0, "moe.routed_pairs": 4 * 2048 * 4}
    counted = real.call_counters(rows=2, lengths=[767, 768], computed=2,
                                 pages=170, max_len=6144, platform="tpu")
    assert counted["mla.layer_steps"] == counted["mla.kernel_steps"] == 4
    assert counted["mla.ring_bytes"] == 4 * 1280 * (768 + 1536) \
        == family.ring_bytes(json.load(open(REAL)), [767, 768])
    assert counted["cache.latent_bytes"] == 170 * 4 * 1280 * 6144
    off = real.call_counters(rows=2, lengths=[767, 768], computed=2,
                             pages=170, max_len=6144, platform="cpu")
    assert off["mla.kernel_steps"] == 0
    assert off["mla.ring_bytes"] == 4 * 1280 * 2 * 6144


def test_the_tenant_charges_and_counts_the_latent_ring(held):
    """`add_generative_tenant` charges the one ring a layer; two requests
    through `admit` / `decode_step` move `kv.*` (a latent ring counts as a
    ring, with no second ring beside it), `cache.*` and `mla.*`."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("kv.page_positions", "kv.reserved_positions",
             "kv.used_positions", "kv.skipped_positions",
             "cache.reserved_bytes", "cache.latent_bytes",
             "cache.state_bytes", "mla.layer_steps", "mla.kernel_steps",
             "mla.ring_bytes", "moe.routed_pairs",
             "serving.decode.dispatches")
    server = mx.serving.ModelServer({})
    try:
        session = server.add_generative_tenant(
            "lm", family.model(CONFIG), held, ctx=mx.cpu(), max_sessions=2,
            max_len=128, max_decode_tokens=8, seq_buckets=[8, 32])
        page = 2 * 4 * 32 * 128
        assert session._cache_bytes == 3 * page
        assert telemetry.snapshot()["gauges"]["kv.ring_bytes"] \
            == 3 * page + session._state[-1].nbytes
        before = {n: telemetry.counter_value(n) for n in names}
        futs = [server.submit_generate("lm", TOKENS[:n], max_new_tokens=6)
                for n in (5, 20)]
        for f in futs:
            assert len(f.result(timeout=120).tokens) == 6
        moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    finally:
        server.close()
        telemetry.set_enabled(was)
    steps = moved["serving.decode.dispatches"]
    assert steps >= 5
    rows = 2 * 5     # each session's five decode steps
    assert moved["kv.page_positions"] == rows * 128
    assert moved["kv.skipped_positions"] == 0      # off the TPU: whole pages
    assert 0 < moved["kv.used_positions"] < moved["kv.reserved_positions"]
    assert moved["mla.layer_steps"] == 2 * steps
    assert moved["mla.kernel_steps"] == 0
    assert moved["mla.ring_bytes"] == 2 * rows * 4 * 32 * 128
    assert moved["cache.latent_bytes"] == moved["cache.reserved_bytes"] > 0
    assert moved["cache.state_bytes"] == 0


# ----------------------------------------------------------------------
# one chip's share of the expert layer
# ----------------------------------------------------------------------

def _expert_layer(p, i, first, count, shared, x):
    """Layer i's `mx.sym.MoE` node alone on `x (T, d)`, holding experts
    `first` .. `first + count`, with or without the shared expert."""
    names = ["router_weight", "gate_weight", "down_weight", "up_weight"]
    if shared:
        names += ["shared_gate_weight", "shared_down_weight",
                  "shared_up_weight"]
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    node = mx.sym.MoE(*v, num_experts=16, hidden_size=32, k=2,
                      act_type="silu", gated=True, no_bias=True,
                      normalize=True, held_first=first, held_count=count,
                      shared_size=32 if shared else 0, return_load=True)
    values = {n: np.asarray(p["l%d_%s" % (i, n)]) for n in names}
    for n in ("gate_weight", "down_weight", "up_weight"):
        values[n] = values[n][first:first + count]
    exe = node.bind(mx.cpu(), dict({"data": mx.nd.array(x)}, **{
        n: mx.nd.array(a) for n, a in values.items()}), grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


def test_the_eight_shares_and_the_shared_expert_once_make_the_layer(uncut):
    """THE SHARE TEST: the outputs of one expert layer held as experts
    0-1, 2-3, ... 14-15 (eight chips a layer, the router 16 wide, 2 a
    token, renormalised over the two, on all), the shared expert counted
    once, add up to what the uncut reference gives for the whole layer;
    each share's load counts its own experts' pairs, which together are
    every pair."""
    import jax

    x = np.random.default_rng(2).standard_normal((24, 64)).astype(np.float32)
    layer = lambda n: uncut["l1_%s" % n]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.expert_layer(
            x, layer("router_weight"), layer("gate_weight"),
            layer("up_weight"), layer("down_weight"),
            (layer("shared_gate_weight"), layer("shared_up_weight"),
             layer("shared_down_weight")), 2, True, 1.0, "softmax", 0)[0])
    parts = [_expert_layer(uncut, 1, first, 2, first == 0, x)
             for first in range(0, 16, 2)]
    _close(sum(out for out, _ in parts), want, 1e-5)
    assert all(load.shape == (2,) for _, load in parts)
    assert sum(load.sum() for _, load in parts) == 24 * 2
    # no share is the layer: the other chips' terms are LEFT OUT
    assert np.abs(parts[0][0] - want).max() > 1e-2 * np.abs(want).max()
    # and the shared expert on every chip would count it eight times
    twice = _expert_layer(uncut, 1, 2, 2, True, x)[0] + sum(
        out for out, _ in parts) - parts[1][0]
    assert np.abs(twice - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("held,near", [((0, 2), False), ((2, 2), True),
                                       ((3, 3), True), ((4, 4), False),
                                       ((0, None), True)])
def test_a_near_tie_counts_where_a_held_expert_is_at_the_edge(held, near):
    """`reference.route`'s margin: experts 2 and 3 tie at the edge of a
    choice of three.  The chip that holds either is near a tie; one that
    holds neither is as far from it as its own experts are from the edge
    — its terms stay the same whichever of the two is kept."""
    logits = np.asarray([[5.0, 4.0, 3.0, 2.99, 0.0, -1.0, -1.0, -2.0]],
                        np.float32)
    weights, margin = reference.route(logits, np.eye(8, dtype=np.float32),
                                      3, True, 1.0, "softmax", *held)
    assert np.flatnonzero(np.asarray(weights)[0]).tolist() == [0, 1, 2]
    np.testing.assert_allclose(np.asarray(weights).sum(), 1.0, rtol=1e-6)
    if near:
        np.testing.assert_allclose(margin[0], 1 - np.exp(-0.01), rtol=1e-3)
    else:
        assert margin[0] > family.NEAR_TIE


# ----------------------------------------------------------------------
# the cell's own check, tiny, and the faults it must refuse
# ----------------------------------------------------------------------

def _check(held_params, params, config=CONFIG, lm=None):
    """`families/mistral4.py check_against_reference` on a four-slot
    tenant: one long, one short, two mid rows, 40 steps of the four-row
    decode program; `params` is what the reference is given."""
    session = _session(held_params, config, lm)
    try:
        return family.check_against_reference(CONFIG, session, params, 11,
                                              8, steps=40)
    finally:
        session.close()


def test_the_check_passes_the_sound_program(params, held):
    ok, facts = _check(held, params)
    assert ok, facts
    assert facts["compared"] >= 40 and facts["rows_a_step"] == 4
    assert facts["logit_rel_err_worst"] < RTOL
    assert facts["router_rel_err"] <= family.ROUTER_RTOL


def _unnormed(only_decode):
    """`TransformerLM._norm` that leaves `c_kv` as it is — in every graph,
    or in the decode graph alone."""
    real = TransformerLM._norm

    def norm(self, x, name, width=None):
        skip = name.endswith("_kva_norm") and (
            not only_decode or getattr(self, "_decoding", False))
        if not skip:
            return real(self, x, name, width)
        # keep the gain a parameter of the graph
        return x + 0 * real(self, x, name, width)
    return norm


class _DecodeFlag(TransformerLM):
    def decode_symbol(self):
        self._decoding = True
        try:
            return super().decode_symbol()
        finally:
            self._decoding = False


def _op_fault(name, change):
    """The registered op `name` with its operands changed by `change`."""
    op = get_op(name)
    real = op.fn
    return mock.patch.object(
        op, "fn", lambda *operands, **kw: real(*change(*operands), **kw))


def _k_r_per_head(q_nope, q_rope, *rest):
    """As if every head had a rotary key of its own — head h's the shared
    one with its channels rolled by h: ``q_h . (P_h k_r) = (P_h^T q_h) .
    k_r``."""
    import jax.numpy as jnp

    n, t, _ = q_rope.shape
    heads = q_rope.reshape(n, t, 4, 8)
    rolled = jnp.stack([jnp.roll(heads[:, :, h], h, axis=-1)
                        for h in range(4)], axis=2)
    return (q_nope, rolled.reshape(n, t, -1)) + rest


def _value_from_all_lines():
    """The decode step's context summed over ALL lines of a row, the
    rotary key's folded onto the first ones, where the value is the
    first `rank` alone."""
    real = latent._latent_decode

    def decode(q, new, cache, slot_i, len_i, *, rank, scale, block,
               interpret):
        u, ring = real(q, new, cache, slot_i, len_i, rank=cache.shape[2],
                       scale=scale, block=block, interpret=interpret)
        extra = u.shape[-1] - rank
        return u[..., :rank].at[..., :extra].add(u[..., rank:]), ring
    return mock.patch.object(latent, "_latent_decode", decode)


FAULTS = ["sigma_without_mscale", "unpermuted_rows", "plain_rope",
          "no_kva_norm", "absorbed_reads_unnormed", "k_r_per_head",
          "value_from_all_lines", "shared_twice", "absent_not_masked"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_refuses_each_seeded_fault(fault, params, uncut, held):
    """Each of the faults ISSUE 44 names, in the program and not in the
    reference: the check that passes the sound program refuses it."""
    import contextlib

    config, handed, lm, stack = CONFIG, held, None, contextlib.ExitStack()
    if fault == "sigma_without_mscale":
        config = dict(CONFIG, assumed=dict(
            CONFIG["assumed"], softmax_scale={"value": 16 ** -0.5}))
    elif fault == "unpermuted_rows":
        handed = _hold(family.checkpoint_layout(params, CONFIG))
    elif fault == "plain_rope":
        lm = TransformerLM(**dict(family.model_args(CONFIG),
                                  rope_scaling=None))
    elif fault == "no_kva_norm":
        stack.enter_context(mock.patch.object(TransformerLM, "_norm",
                                              _unnormed(False)))
    elif fault == "absorbed_reads_unnormed":
        stack.enter_context(mock.patch.object(TransformerLM, "_norm",
                                              _unnormed(True)))
        lm = _DecodeFlag(**family.model_args(CONFIG))
    elif fault == "k_r_per_head":
        for name in ("_latent_attention", "_latent_cached_attention"):
            stack.enter_context(_op_fault(name, _k_r_per_head))
    elif fault == "value_from_all_lines":
        stack.enter_context(_value_from_all_lines())
    elif fault == "shared_twice":
        handed = _hold({k: 2 * v if k.endswith("shared_down_weight") else v
                        for k, v in params.items()})
    elif fault == "absent_not_masked":
        # the program computes the pairs of experts it was not given: it
        # holds all sixteen where the share, and the reference, hold four
        config, handed = UNCUT, _hold(uncut)
    with stack:
        ok, facts = _check(handed, params, config, lm)
    assert not ok, facts
    assert max(facts["logit_rel_err"], facts["logit_rel_err_high"]) \
        > family.LOGIT_RTOL


# ----------------------------------------------------------------------
# the spec
# ----------------------------------------------------------------------

def test_a_latent_layer_needs_its_sizes():
    base = dict(vocab=8, num_layers=1, layer_types=["latent_attention"],
                norm="rms", latent_q_rank=8, latent_kv_rank=8,
                latent_nope_dim=4, latent_rope_dim=4, latent_value_dim=8)
    TransformerLM(**base)
    with pytest.raises(ValueError, match="latent_q_rank"):
        TransformerLM(**dict(base, latent_kv_rank=0))
    with pytest.raises(ValueError, match="even latent_rope_dim"):
        TransformerLM(**dict(base, latent_rope_dim=3, latent_value_dim=7))
    with pytest.raises(ValueError, match="one width"):
        TransformerLM(**dict(base, latent_value_dim=16))
    with pytest.raises(ValueError, match="YaRN"):
        TransformerLM(**dict(base, rope_scaling={"factor": 2}))
    with pytest.raises(ValueError, match="query_scale"):
        TransformerLM(**dict(base, query_scale=(0.1, 0)))


def test_the_configuration_keeps_every_published_width():
    """The file at the published widths, cut in depth, experts held and
    vocabulary alone; sigma under `assumed` is the stated formula."""
    with open(REAL) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    want = {"hidden_size": 4096, "num_attention_heads": 32,
            "q_lora_rank": 1024, "kv_lora_rank": 256, "qk_nope_head_dim": 64,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "moe_intermediate_size": 2048, "num_experts_per_tok": 4,
            "router_experts": 128, "n_shared_experts": 1,
            "num_hidden_layers": 4, "n_routed_experts": 16,
            "vocab_size": 16384, "held_experts": [0, 16]}
    assert {k: config[k] for k in want} == want
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["published"]["n_routed_experts"] == 128
    rope = config["rope_parameters"]
    assert (rope["factor"], rope["original_max_position_embeddings"],
            rope["beta_fast"], rope["beta_slow"]) == (128, 8192, 32, 1)
    assert config["assumed"]["softmax_scale"]["value"] == pytest.approx(
        _sigma(128, rope), rel=1e-12)
    assert config["assumed"]["softmax_scale"]["value"] == pytest.approx(
        0.19497, abs=1e-5)
    shapes = family.param_shapes(config)
    assert shapes["l0_kva_weight"] == (320, 4096)
    assert shapes["l3_gate_weight"] == (16, 4096, 2048)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert 7.83e9 < 4 * total < 7.85e9
