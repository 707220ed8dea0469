"""OLMoE's block through `TransformerLM`: RMSNorm, rotary positions,
QK-norm, a dropless top-k routed SwiGLU layer, no biases, untied head —
against the plain reference of the benchmark
(benchmarks/reference/olmoe.py: float32 `jax.numpy` at "highest", a
Python loop over each token's experts, independent of `mxnet_tpu`).

Tiny widths, both sides float32 on the CPU, where XLA multiplies in
float32: the errors are float32 rounding over two layers (measured 7e-7
of the largest logit for one forward, 1.2e-6 through the ring); the
bound 1e-4 is a hundred times that and a fortieth of what one bfloat16
pass (2^-9 a product) leaves.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.serving import GenerativeSession

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.families import olmoe as family  # noqa: E402
from benchmarks.reference import olmoe as reference  # noqa: E402

CONFIG = {"vocab_size": 96, "num_hidden_layers": 2,
          "num_attention_heads": 4, "hidden_size": 64,
          "intermediate_size": 32, "max_position_embeddings": 48,
          "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_experts": 8,
          "num_experts_per_tok": 2, "param_dtype": "float32"}
RTOL = 1e-4  # of the largest |logit|; see the module docstring


@pytest.fixture(scope="module")
def params():
    import jax

    # the init's 0.02 gives near-uniform routers and tiny expert terms;
    # x8 makes every part of the block matter to the logits
    p = family.make_params(CONFIG, 5, jax.devices("cpu")[0])
    return {k: v if k.endswith("_gamma") else 8.0 * v for k, v in p.items()}


@pytest.fixture(scope="module")
def held(params):
    return {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max() / np.abs(want).max())


def _score(lm, held, tokens):
    t = len(tokens)
    pred = mx.Predictor(lm.score_symbol(), dict(held), {"data": (1, t)})
    pred.forward(data=np.asarray([tokens], np.float32))
    return pred.get_output(0).reshape(t, lm.vocab)


def test_rmsnorm_and_rotary_against_numpy():
    """The two new ops alone: RMSNorm's formula, rotate-half over the
    whole head at positions 0..T-1, and `_rotary_at` placing a row's
    first token at its own traced index."""
    rng = np.random.default_rng(4)
    n, t, heads, dh = 2, 5, 3, 8
    x = rng.normal(size=(n, t, heads * dh)).astype(np.float32)
    gamma = rng.normal(size=(heads * dh,)).astype(np.float32)
    got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(gamma), eps=1e-5)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * gamma
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-6)

    def turned(x, pos):  # x (T, heads*dh), pos (T,)
        xh = x.reshape(len(pos), heads, dh)
        ang = pos[:, None, None] * 100.0 ** (-np.arange(dh // 2) / (dh // 2))
        cos, sin = np.cos(ang), np.sin(ang)
        a, b = xh[..., :dh // 2], xh[..., dh // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1).reshape(len(pos), heads * dh)

    got = mx.nd._rotary(mx.nd.array(x), num_heads=heads, theta=100.0)
    for row in range(n):
        np.testing.assert_allclose(got.asnumpy()[row],
                                   turned(x[row], np.arange(t)),
                                   rtol=1e-5, atol=1e-5)
    index = np.asarray([7, 3], np.float32)
    got = mx.nd._rotary_at(mx.nd.array(x), mx.nd.array(index),
                           num_heads=heads, theta=100.0)
    for row in range(n):
        np.testing.assert_allclose(
            got.asnumpy()[row], turned(x[row], index[row] + np.arange(t)),
            rtol=1e-5, atol=1e-5)


def test_score_logits_match_reference(params, held):
    """(a) one full forward, every position's logits."""
    lm = family.model(CONFIG)
    toks = np.random.default_rng(0).integers(0, lm.vocab, 20).tolist()
    _close(_score(lm, held, toks), reference.logits(params, CONFIG, toks))


def test_prefill_and_decode_through_the_ring_match_reference(params, held):
    """(b) two sessions in different slots: prefill into the ring (a
    padded bucket), then 9 decode steps each, interleaved in one packed
    batch — every step's logits against ONE full forward of the
    reference over the final sequence."""
    lm = family.model(CONFIG)
    gs = GenerativeSession("lm", lm, held, max_sessions=2, max_len=48,
                           seq_buckets=[16])
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, lm.vocab, n).tolist() for n in (5, 11)]
    starts = [len(s) for s in seqs]
    got = [[], []]
    exe, fn = gs._program(gs._prefill_pred, 1, 16, True)
    for slot, toks in enumerate(seqs):
        data = np.zeros((1, 16), np.float32)
        data[0, :len(toks)] = toks
        got[slot].append(gs._run(exe, fn, data,
                                 np.full((1,), slot, np.float32),
                                 np.full((1,), len(toks), np.float32))[0])
    exe, fn = gs._program(gs._decode_pred, 2, 1, False)
    for _ in range(9):
        for slot in (0, 1):
            seqs[slot].append(int(np.argmax(got[slot][-1])))
        logits = gs._run(
            exe, fn, np.asarray([[s[-1]] for s in seqs], np.float32),
            np.asarray([0, 1], np.float32),
            np.asarray([len(s) - 1 for s in seqs], np.float32))
        for slot in (0, 1):
            got[slot].append(logits[slot])
    for slot in (0, 1):
        ref = np.asarray(reference.logits(params, CONFIG, seqs[slot]))
        for i, row in enumerate(got[slot]):
            _close(row, ref[starts[slot] - 1 + i])


def test_dropless_when_every_token_picks_the_same_experts():
    """(c) a batch whose tokens all choose the same two experts: the
    dropless layer computes every pair (its load says so) and matches
    the dense formula; the capacity-bounded mode of the same op drops
    most of them."""
    rng = np.random.default_rng(2)
    T, D, H, E, k = 16, 8, 12, 8, 2
    x = np.abs(rng.normal(size=(T, D))).astype(np.float32)
    gw = np.zeros((D, E), np.float32)
    gw[:, 3], gw[:, 5] = 1.0, 0.5          # every token: experts 3 and 5
    w1, w3 = (rng.normal(size=(E, D, H)).astype(np.float32) for _ in "ab")
    w2 = rng.normal(size=(E, H, D)).astype(np.float32)
    attrs = dict(num_experts=E, hidden_size=H, k=k, act_type="silu",
                 gated=True, no_bias=True, normalize=False)
    args = [mx.nd.array(a) for a in (x, gw, w1, w2, w3)]
    out, load = mx.nd.MoE(*args, return_load=True, **attrs)
    want_load = np.zeros(E)
    want_load[[3, 5]] = T
    np.testing.assert_array_equal(load.asnumpy(), want_load)
    logits = x @ gw
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)

    def silu(a):
        return a / (1.0 + np.exp(-a))

    want = sum(p[:, e:e + 1] * ((silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
               for e in (3, 5))
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)
    bounded = mx.nd.MoE(*args, capacity_factor=1.0, **attrs).asnumpy()
    capacity = k * T // E
    assert np.abs(bounded[capacity:]).max() == 0.0  # tokens past capacity
    assert np.abs(out.asnumpy()[capacity:]).min() > 0.0


# (pairs, experts scored) of the routed cells' programs -> rows spared: a
# mixed step's (bucket + rows) x k pairs, a decode step's, a whole bucket's
SPARED = {"olmoe 64": (72 * 8, 64, 0), "olmoe 128": (136 * 8, 64, 0),
          "olmoe 256": (264 * 8, 64, 448), "olmoe 512": (520 * 8, 64, 448),
          "olmoe step": (8 * 8, 64, 0), "olmoe prefill": (256 * 8, 64, 0),
          "qwen3-next 1024": (1040 * 10, 512, 0),
          "qwen3-next 2048": (2064 * 10, 512, 352),
          "trinity 768": (776 * 8, 128, 448),
          "few experts": (40 * 2, 2, 0)}


@pytest.mark.parametrize("name", sorted(SPARED))
def test_the_pair_rows_an_expert_layer_spares(name):
    """Two dozen pairs an expert scored or more: up to whole 512-row
    tiles; fewer, or less than one such tile: none."""
    from mxnet_tpu.parallel import moe

    pairs, experts, spare = SPARED[name]
    assert moe._spare_rows(pairs, experts) == spare


@pytest.mark.parametrize("tokens", [264, 520])
@pytest.mark.parametrize("held", [None, (16, 32)])
@pytest.mark.parametrize("biased", [False, True])
def test_pairs_are_gathered_to_whole_row_tiles(tokens, held, biased,
                                               monkeypatch):
    """PR 53: the TPU's grouped matmul walks the pair rows by the largest
    power-of-two tile, up to 512, that divides their count, so `_dropless`
    gathers MORE rows than it has pairs where an expert gets two dozen or
    more (264 and 520 positions at eight of 64 experts a token: a mixed
    step's riders behind its 256 and 512 buckets).  The rows past the last
    pair lie beyond every segment, where a TPU's segment matmul leaves
    stale memory — NaN here, forward and backward: the same output, load
    and gradient as the pairs alone, with biases, and with a held range of
    the experts."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    dot = jax.lax.ragged_dot

    def beyond(rows, groups, fill):
        live = jnp.arange(rows.shape[0]) < groups.sum()
        return jnp.where(live[:, None], rows, fill)

    @jax.custom_vjp
    def stale(lhs, rhs, groups):
        assert lhs.shape[0] == tokens * k + 448
        return beyond(dot(beyond(lhs, groups, 0), rhs, groups), groups,
                      jnp.nan)

    def forward(lhs, rhs, groups):
        return stale(lhs, rhs, groups), (lhs, rhs, groups)

    def backward(saved, g):
        lhs, rhs, groups = saved
        d_lhs, d_rhs = jax.vjp(lambda a, b: dot(a, b, groups),
                               beyond(lhs, groups, 0), rhs)[1](
                                   beyond(g, groups, 0))
        return beyond(d_lhs, groups, jnp.nan), d_rhs, None

    stale.defvjp(forward, backward)
    rng = np.random.default_rng(3)
    D, H, E, k = 16, 8, 64, 8
    assert moe._spare_rows(tokens * k, E) == 448
    x = jnp.asarray(rng.normal(size=(tokens, D)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(tokens, E)).astype(np.float32))
    first, count = held or (0, E)
    weights = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                    for shape in ((count, D, H), (count, H, D),
                                  (count, D, H)))
    biases = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                   for shape in ((count, H), (count, D), (count, H))
                   ) if biased else None

    def layer(x, weights, biases):
        return moe.dropless_experts(x, logits, k, weights, biases,
                                    act="silu", gated=True, held=held)

    def both():
        return layer(x, weights, biases), jax.grad(
            lambda *args: layer(*args)[0].sum(), (0, 1, 2))(
                x, weights, biases)

    plain_spare = moe._spare_rows
    # (a held layer's pairs of experts held elsewhere lie beyond every
    # segment too, and only the forward pass zeroes them: the parent's;
    # since PR 55 this many pairs of a held range go through
    # `_held_passes` — the next test — so the held case is kept on the
    # path that gathers every pair's row)
    monkeypatch.setattr(moe, "_COMPACT_PAIRS", 1 << 30)
    if held is None:
        monkeypatch.setattr(jax.lax, "ragged_dot", stale)
    gathered = both()
    monkeypatch.setattr(moe, "_spare_rows", lambda pairs, experts: 0)
    monkeypatch.setattr(jax.lax, "ragged_dot", dot)
    plain = both()
    assert plain_spare is not moe._spare_rows
    assert float(jnp.abs(plain[0][0]).max()) > 1.0
    assert 0 < float(plain[0][1].sum()) <= tokens * k
    for a, b in zip(jax.tree_util.tree_leaves((gathered[0], gathered[1][0])),
                    jax.tree_util.tree_leaves((plain[0], plain[1][0]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (a weight's gradient sums over the rows, in another order over more)
    for a, b in zip(jax.tree_util.tree_leaves(gathered[1][1:]),
                    jax.tree_util.tree_leaves(plain[1][1:])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


# The held pairs alone (PR 55), at a row tile of 8 and a threshold of 16 so
# that the shapes stay tiny: 16 experts scored, experts 2-5 held, four a
# token — a pass takes ceil(1.5 x pairs / 4 / 8) x 8 sorted rows.  A case:
# (tokens, tokens whose four are ALL held, tokens with ONE held pair beside
# them, passes the held pairs fill); None draws the routing from noise.
HELD_PASSES = {"near uniform": (40, None, None, 1),
               "with biases": (40, 16, 1, 2),
               "every pair held": (40, 40, 0, 3),
               "no pair held": (40, 0, 0, 0),
               "exactly one pass": (40, 16, 0, 1),
               "one pass and one pair": (40, 16, 1, 2),
               "a mixed step's odd count": (37 + 3, 5, 7, 1),
               "in pieces before": (48, None, None, 1)}


@pytest.mark.parametrize("name", sorted(HELD_PASSES))
def test_a_held_range_walks_the_pair_rows_it_holds(name, monkeypatch):
    """PR 55: under a held range `_dropless` sorts all T x k pairs (the
    narrow work) and gathers, multiplies and returns to token order the
    HELD pairs alone, in passes of a static count of sorted rows, as many
    as the load fills — none dropped, whatever the routing.  Against the
    path that gathers every pair's row (forced by the threshold): the
    same output to float32 rounding (a token's pairs are summed in
    another order), the same load exactly, the same gradient in x, the
    router's scores and the expert weights — with a grouped matmul that
    leaves NaN beyond its segments, forward and backward, as a TPU's
    leaves stale memory."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    tokens, all_held, one_held, passes = HELD_PASSES[name]
    D, H, E, k, held = 16, 8, 16, 4, (2, 4)
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    monkeypatch.setattr(moe, "_COMPACT_PAIRS", 16)
    rows = moe._pass_rows(tokens * k, held, E)
    assert rows == {40: 64, 48: 72}[tokens]
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(tokens, E)).astype(np.float32)
    if all_held is not None:
        # the four largest: held experts for the first `all_held` tokens,
        # one held and three others for the next `one_held`, others after
        logits[:, 2:6] -= 20
        logits[:all_held, 2:6] += 40
        logits[all_held:all_held + one_held, 3] += 40
    x = jnp.asarray(rng.normal(size=(tokens, D)).astype(np.float32))
    logits = jnp.asarray(logits)
    weights = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                    for shape in ((4, D, H), (4, H, D), (4, D, H)))
    biases = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                   for shape in ((4, H), (4, D), (4, H))
                   ) if name == "with biases" else None
    dot = jax.lax.ragged_dot
    calls = []

    def beyond(r, groups, fill):
        return jnp.where((jnp.arange(r.shape[0]) < groups.sum())[:, None],
                         r, fill)

    @jax.custom_vjp
    def stale(lhs, rhs, groups):
        calls.append(lhs.shape[0])
        return beyond(dot(beyond(lhs, groups, 0), rhs, groups), groups,
                      jnp.nan)

    def backward(saved, g):
        lhs, rhs, groups = saved
        d_lhs, d_rhs = jax.vjp(lambda a, b: dot(a, b, groups),
                               beyond(lhs, groups, 0), rhs)[1](
                                   beyond(g, groups, 0))
        return beyond(d_lhs, groups, jnp.nan), d_rhs, None

    stale.defvjp(lambda *a: (stale(*a), a), backward)

    def layer(x, logits, weights, biases):
        return moe.dropless_experts(x, logits, k, weights, biases,
                                    act="silu", gated=True, held=held)

    def both():
        return layer(x, logits, weights, biases), jax.grad(
            lambda *args: (layer(*args)[0] ** 2).sum(), (0, 1, 2, 3))(
                x, logits, weights, biases)

    pieces = []
    whole = moe._dropless
    monkeypatch.setattr(moe, "_dropless", lambda x, *a: (
        pieces.append(x.shape[0]), whole(x, *a))[1])
    if name == "in pieces before":
        # every pair's 192 rows pass it, a pass's 72 do not: two pieces of
        # the tokens at the parent, one now
        monkeypatch.setattr(moe, "_PAIR_BYTES", 100 * D * 4)
    monkeypatch.setattr(jax.lax, "ragged_dot", stale)
    (out, load), grads = both()
    assert set(calls) == {rows} and pieces == [tokens, tokens]
    monkeypatch.setattr(jax.lax, "ragged_dot", dot)
    monkeypatch.setattr(moe, "_COMPACT_PAIRS", 1 << 30)
    (want, want_load), want_grads = both()
    if name == "in pieces before":
        assert pieces[2:] == [24] * 2   # lax.map traces a piece once a call
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert -(-int(load.sum()) // rows) == passes
    if all_held is not None:
        assert int(load.sum()) == k * all_held + one_held
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert passes == 0 or float(jnp.abs(want).max()) > 0.1
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        scale = max(float(jnp.abs(b).max()), 1.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * scale)


def test_a_pass_past_the_pair_bytes_still_goes_in_pieces(monkeypatch):
    """`dropless_experts` reckons what a call GATHERS: where one pass's
    rows pass `_PAIR_BYTES` the tokens are cut again, each piece walking
    its own held pairs."""
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    monkeypatch.setattr(moe, "_COMPACT_PAIRS", 16)
    row = 16 * 4
    assert moe.pass_plan(48, 4, row, (2, 4), 16) == (1, 72)
    monkeypatch.setattr(moe, "_PAIR_BYTES", 71 * row)
    assert moe.pass_plan(48, 4, row, (2, 4), 16) == (2, 40)
    # no held range, or few pairs: every pair's row, as before
    assert moe.pass_plan(48, 4, row, None, 16) == (3, 0)   # 64 <= 71 rows
    assert moe.pass_plan(3, 4, row, (2, 4), 16) == (1, 0)
    with pytest.raises(ValueError, match="no piece"):
        moe.pass_plan(48, 4, 18 * row, (2, 4), 16)


def test_training_loss_and_gradients_match_reference(params, held):
    """(d) the training graph's loss gradient for router, expert,
    attention and norm weights against `jax.grad` of the reference's
    mean cross-entropy."""
    import jax
    import jax.numpy as jnp

    lm = family.model(CONFIG)
    rng = np.random.default_rng(3)
    n, t = 2, 12
    data = rng.integers(0, lm.vocab, (n, t))
    label = rng.integers(0, lm.vocab, (n, t))
    watch = ["l0_router_weight", "l1_gate_weight", "l0_up_weight",
             "l1_down_weight", "l0_qkv_weight", "l1_out_weight",
             "l0_qnorm_gamma", "head_weight", "embed_weight"]

    def loss(p):
        total = 0.0
        for row, lab in zip(data, label):
            logp = jax.nn.log_softmax(
                reference.logits(p, CONFIG, row.tolist()), axis=-1)
            total = total - jnp.take_along_axis(
                logp, jnp.asarray(lab)[:, None], axis=-1).sum()
        return total / (n * t)

    want_loss, want = jax.value_and_grad(loss)(params)
    net = lm.training_symbol()
    args = dict(held, data=mx.nd.array(data.astype(np.float32)),
                softmax_label=mx.nd.array(label.astype(np.float32)))
    grads = {k: mx.nd.zeros(v.shape) for k, v in held.items()}
    exe = net.bind(mx.cpu(), args, args_grad=grads)
    exe.forward(is_train=True)
    prob = exe.outputs[0].asnumpy()
    got_loss = -np.log(prob[np.arange(n * t), label.reshape(-1)]).mean()
    np.testing.assert_allclose(got_loss, float(want_loss), rtol=1e-5)
    exe.backward()
    for name in watch:
        # gradients sum float32 products over 24 positions in another
        # order than the reference: 1e-3 of the largest entry (measured
        # 1.4e-6) is far under a wrong or missing path, which is off by
        # the entry's own size
        _close(grads[name].asnumpy(), want[name], rtol=1e-3)


def test_defaults_are_the_opt_block():
    """(e) with no new argument the spec lists OPT's arguments and
    output shapes, and a routed model's serving graphs add exactly one
    output after the rings."""
    lm = TransformerLM(vocab=24, num_layers=2, num_heads=2, d_model=16,
                       max_len=32)
    block = ["ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias",
             "out_weight", "out_bias", "ln2_gamma", "ln2_beta",
             "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias"]
    want = (["data", "embed_weight", "pos_weight"]
            + ["l%d_%s" % (i, n) for i in (0, 1) for n in block]
            + ["ln_f_gamma", "ln_f_beta"])
    assert lm.score_symbol().list_arguments() == want
    assert lm.training_symbol().list_arguments() == want + ["softmax_label"]
    assert lm.extra_outputs() == ()
    spec = lm.cache_spec(3)
    ring = spec["k_cache_0"].shape
    shapes = dict(data=(2, 1), slot=(2,), length=(2,), last_token=(3,),
                  **{n: e.shape for n, e in spec.items()})
    # logits, the rings, each slot's last token, the sampled tokens
    _, outs, _ = lm.decode_symbol().infer_shape(**shapes)
    assert outs == [(2, 24)] + [ring] * 4 + [(3,), (2,)]
    shapes.update(data=(1, 8), slot=(1,), length=(1,))
    _, outs, _ = lm.prefill_symbol().infer_shape(**shapes)
    assert outs == [(1, 24)] + [ring] * 4 + [(3,), (1,)]

    routed = family.model(CONFIG)
    assert routed.extra_outputs() == ("moe_load",)
    names = routed.decode_symbol().list_outputs()
    assert len(names) == 1 + 2 * CONFIG["num_hidden_layers"] + 2 + 1
    assert "pos_weight" not in routed.score_symbol().list_arguments()
    assert not any(a.endswith("_bias") or a.endswith("_beta")
                   for a in routed.score_symbol().list_arguments())


def test_runahead_matches_one_at_a_time_greedy_decode_routed(held):
    """The loop one step ahead of the host, on a routed model: same
    tokens, `finish_reason` and `on_token` order as one request at a
    time through `score_symbol` (tests/test_transformer_lm.py)."""
    from test_transformer_lm import assert_runahead_matches_one_at_a_time

    assert_runahead_matches_one_at_a_time(family.model(CONFIG), held,
                                          seq_bucket=16)


def test_batcher_books_the_moe_counters(held):
    """A generation through admit()/decode_step() moves the `moe.*`
    counters by what its programs computed: k pairs a token row, a
    layer's experts a call."""
    lm = family.model(CONFIG)
    telemetry.set_enabled(True)
    before = dict(telemetry.snapshot()["counters"])
    gs = GenerativeSession("lm", lm, held, max_sessions=2, max_len=48,
                           seq_buckets=[16])
    from mxnet_tpu.serving import GenerateRequest

    req = GenerateRequest("lm", [3, 1, 4, 1, 5], 60.0, 3)
    assert gs.admit([req]) == []
    while gs.active():
        gs.decode_step()
    assert len(req.future.result(timeout=0).tokens) == 3
    after = telemetry.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("moe.pairs", "moe.experts_hit", "moe.expert_slots",
                       "moe.max_load", "moe.pair_rows", "moe.passes",
                       "moe.compact_calls")}
    layers, k = CONFIG["num_hidden_layers"], CONFIG["num_experts_per_tok"]
    # one mixed step of 16 positions and its two idle rows, two decode
    # steps of one row
    assert moved["moe.pairs"] == layers * k * (16 + 2 + 1 + 1)
    # every expert is here: a layer gathers every pair's row, in no pass
    assert moved["moe.pair_rows"] == moved["moe.pairs"]
    assert moved["moe.passes"] == moved["moe.compact_calls"] == 0
    assert moved["moe.expert_slots"] == 3 * layers * CONFIG["num_experts"]
    assert 0 < moved["moe.experts_hit"] <= moved["moe.expert_slots"]
    assert moved["moe.max_load"] >= 3 * layers


# (pairs a layer, pieces, a pass's rows, whether the TPU's kernel multiplies
# them, whether its calls fetch and place their own rows, whether a pass's
# rows return to their tokens through the kernel) of a program, its
# `moe_load` summed an expert-layer -> rows gathered, passes, layers that
# walked held pairs
BOOKED = {"one pass a layer": ((5120, 1, 1024, False, False, False),
                               [640, 1024], 2048, 2, 2),
          "three passes": ((5120, 1, 1024, False, False, False), [2049, 0],
                           3072, 3, 2),
          "every pair's row": ((80, 1, 0, False, False, False), [10, 7], 160,
                               0, 0),
          "two pieces": ((122880, 2, 3072, False, False, False), [3840, 6145],
                         18432, 6, 2),
          "passes through the kernel": ((10240, 1, 2048, True, False, False),
                                        [1280, 2049], 6144, 3, 2),
          "passes returned by the kernel": (
              (10240, 1, 2048, True, False, True), [1280, 2049], 6144, 3, 2),
          "passes returned by the kernel, XLA's matmuls": (
              (1280, 1, 512, False, False, True), [500, 0], 512, 1, 2),
          "every pair's row through the kernel": (
              (49200, 2, 0, True, False, False), [49200, 49200], 98400, 0, 0),
          "every pair's row fetched by the kernel": (
              (49200, 2, 0, True, True, False), [49200, 49200], 98400, 0, 0)}


@pytest.mark.parametrize("name", sorted(BOOKED))
def test_the_rows_an_expert_layer_gathered_are_booked_from_its_load(name):
    """`moe.pair_rows` / `moe.passes` / `moe.compact_calls` (PR 55) come
    from what the program returns anyway, its `moe_load`, and the static
    plan of its bucket (`TransformerLM.expert_plan`): where a layer walks
    the held pairs alone, the passes its load filled times a pass's rows
    (no pass for a layer that held no pair; a call in pieces as if its
    pairs lay evenly over them); every pair's row where it does not.  And
    `moe.kernel_rows` (PR 60) the same rows where the plan says the
    program's segment matmuls are the TPU's kernel, nothing where not, and
    `moe.fused_rows` (PR 61) where the kernel's calls fetch and place
    their own rows, `moe.placed_rows` (PR 63) where the passes' rows
    return to their tokens through the kernel."""
    plan, held_pairs, rows, passes, compact = BOOKED[name]
    load = np.zeros((len(held_pairs), 9), np.int64)
    load[:, 0] = [n // 2 for n in held_pairs]
    load[:, 4] = [n - n // 2 for n in held_pairs]
    telemetry.set_enabled(True)
    names = ("moe.pairs", "moe.pair_rows", "moe.passes", "moe.compact_calls",
             "moe.kernel_rows", "moe.fused_rows", "moe.placed_rows")
    before = dict(telemetry.snapshot()["counters"])
    GenerativeSession._book_moe_load(load, plan)
    after = telemetry.snapshot()["counters"]
    assert [after.get(k, 0) - before.get(k, 0) for k in names] == [
        sum(held_pairs), rows, passes, compact, rows if plan[3] else 0,
        rows if plan[4] else 0, rows if plan[5] else 0]


def test_a_programs_expert_plan_follows_its_tokens():
    """Granite-H-Small's shape of the question — nine of 72 experts held,
    ten a token: a 512 bucket walks passes of 1,024 sorted rows, a 128
    bucket of 512, an 8-row decode step gathers its 80 pairs' rows; with
    every expert held nothing is walked in passes."""
    lm = TransformerLM(vocab=32, num_layers=1, num_heads=2, d_model=64,
                       max_len=32, ffn_types=["routed"], num_experts=72,
                       experts_per_token=10, expert_d_ff=16,
                       held_experts=(0, 9))
    assert lm.expert_plan(512) == (5120, 1, 1024, False, False, False)
    assert lm.expert_plan(1024) == (10240, 1, 2048, False, False, False)
    assert lm.expert_plan(128) == (1280, 1, 512, False, False, False)
    assert lm.expert_plan(8) == (80, 1, 0, False, False, False)
    assert family.model(CONFIG).expert_plan(520) == (1040, 1, 0, False,
                                                      False, False)
    # the kernel (PR 60) is the TPU's, from `moe._KERNEL_ROWS` rows an
    # expert held and at widths of whole lane tiles: Granite-H-Small's own
    wide = TransformerLM(vocab=32, num_layers=1, num_heads=2, d_model=4096,
                         max_len=32, ffn_types=["routed"], num_experts=72,
                         experts_per_token=10, expert_d_ff=768,
                         held_experts=(0, 9))
    # — and there (PR 63) a pass's rows return to their tokens through the
    # TPU's kernel too: whole 128-lane tiles of float32, nothing else asked
    assert wide.expert_plan(512) == (5120, 1, 1024, True, False, True)
    assert wide.expert_plan(512, platform="cpu") == (5120, 1, 1024, False,
                                                     False, False)
    assert wide.expert_plan(128) == (1280, 1, 512, True, False, True)
    assert wide.expert_plan(8) == (80, 1, 0, False, False, False)

    # with every expert held (PR 61) the kernel's calls also fetch and
    # place their own rows: SmallThinker's 8,192 mixed step in two pieces
    whole = TransformerLM(vocab=32, num_layers=1, num_heads=2, d_model=2560,
                          max_len=32, ffn_types=["routed"], num_experts=64,
                          experts_per_token=6, expert_d_ff=768)
    assert whole.expert_plan(8200) == (49200, 2, 0, True, True, False)
    assert whole.expert_plan(8200, platform="cpu") == (49200, 2, 0, False,
                                                       False, False)
    assert whole.expert_plan(8) == (48, 1, 0, False, False, False)
