"""dots3-note-prev's block through `TransformerLM` and `GenerativeSession`:
latent attention in BOTH layer kinds, each with its own heads, ranks,
widths and rotary base (`kind_specs`) — a full layer's under a learned
selection (an indexer of 16 heads keeps the 8 best cached rows), a sliding
layer's over a latent ring of 5 that wraps — a key width that is not the
value width, a headwise gate, rescaled latents, a dense SwiGLU in layer 0
and then 2 of 16 sigmoid-routed experts with a selection bias beside a
shared one, of which this model holds a quarter — against the plain
reference of the benchmark (benchmarks/reference/dots3.py: float32
`jax.numpy` at "highest", the selection and the window as masks, the
checkpoint's head-by-head `W_qb`, independent of `mxnet_tpu`).

Tiny widths (benchmarks/tests/data/rehearsal/configs/dots3_tiny.json: 5
layers, hidden 64), contexts of 40 and more so that the selection binds
and the rings wrap, both sides float32 on the CPU: errors are float32
rounding (measured 1e-6 of the largest logit); the bound 1e-4 is far above
that and far below what one bfloat16 pass leaves.  The file costs about
60 s.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import sparse_latent
from mxnet_tpu.serving import GenerativeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import dots3 as family  # noqa: E402
from benchmarks.reference import dots3 as reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "rehearsal",
                       "configs", "dots3_tiny.json")) as f:
    CONFIG = json.load(f)
UNCUT = dict(CONFIG, n_routed_experts=16, held_experts=[0, 16])
RTOL = 1e-4
SPARSE, WINDOW = "sparse_latent_attention", "window_latent_attention"


def _params(config):
    import jax

    drawn = family.make_params(config, 3, jax.devices("cpu")[0])
    # the init's 0.02 is small against the gains at these widths; x10
    # makes every part of the block matter (the router's logits are of
    # their published size already, and the embedding's rows, which the
    # family draws large so that the chip's rounding at the edge of the
    # selection stays small, are of the matrices' size here: on the CPU
    # both sides choose the same rows)
    return {k: np.asarray(v if k.endswith(("_gamma", "_beta", "_bias",
                                           "_router_weight"))
                          else 0.2 / family.EMBED_STD * v
                          if k == "embed_weight" else 10.0 * v)
            for k, v in drawn.items()}


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
    return {k: mx.nd.array(v) for k, v in params.items()}


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
                   seq_buckets=[64]), **kw)
    return GenerativeSession("lm", lm or family.model(config), held, **kw)


def _want(p, config, tokens):
    return np.asarray(reference.logits(family.checkpoint_layout(p, config),
                                       config, tokens))


TOKENS = [int(t) for t in np.random.default_rng(1).integers(0, 101, 88)]


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "another_share", "uncut"])
def test_score_symbol_matches_the_reference(which, uncut):
    """The full-sequence graph (the up-projected form under the two
    masks), 56 positions — 48 past the selection's 8 and eleven windows —
    for the share the cell holds, another chip's share, and the uncut
    model."""
    config, p = {"share": (CONFIG, _share(uncut, 0, 4)),
                 "another_share": (dict(CONFIG, held_experts=[8, 4]),
                                   _share(uncut, 8, 4)),
                 "uncut": (UNCUT, uncut)}[which]
    got = _score(family.model(config), _hold(p), TOKENS[:56])
    _close(got, _want(p, config, TOKENS[:56]))


@pytest.mark.parametrize("prompt", [3, 40, 64])
def test_prefill_then_decode_through_the_caches_matches_the_reference(
        prompt, params, held):
    """A prompt through the bucket's prefill program — its rows into the
    latent rings, the index keys and the window rings, a prompt longer
    than the window's 5 into a ring that has wrapped — then 24 decode
    steps, each the absorbed form over the 8 gathered rows and over the
    wrapped rings, against ONE forward of the reference: the 3-token
    prompt's steps begin with every row selected and cross into the
    selection at the 8th position."""
    session = _session(held)
    try:
        tokens = TOKENS[:prompt + 24]
        exe, fn = session._program(session._prefill_pred, 1, 64, True)
        data = np.zeros((1, 64), np.float32)
        data[0, :prompt] = tokens[:prompt]
        got = [session._run(exe, fn, data, np.full((1,), 2, np.float32),
                            np.full((1,), prompt, np.float32))[0]]
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        for n in range(prompt, prompt + 23):
            got.append(session._run(
                exe, fn, np.full((1, 1), tokens[n], np.float32),
                np.full((1,), 2, np.float32),
                np.full((1,), n, np.float32))[0])
    finally:
        session.close()
    _close(np.stack(got), _want(params, CONFIG, tokens[:-1])[prompt - 1:])


def test_the_check_passes_the_sound_program_and_reads_the_caches(params,
                                                                 held):
    """The family's check at the tiny size: four rows, every slot live,
    the 72 steps of the four-row program the rings of 128 leave; every cache entry's rows are the
    reference's (a window ring's where `position mod 5` puts them), the
    program's index keys choose the reference's rows, and the reference
    in bfloat16 is refused."""
    session = _session(held)
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5)
        assert ok, facts
        assert facts["prompts"] == [56, 9, 26, 45] and facts["steps"] == 72
        assert facts["logit_rel_err_worst"] < RTOL
        assert set(facts["cache_rel_errs"]) == set(session._spec)
        assert facts["cache_rel_err"] < RTOL
        assert facts["selection_overlap"] == 1.0
        assert facts["compared"] + facts["skipped"] == 4 * 73
        assert facts["compared"] > 100
        ok, facts = family.check_against_reference(
            CONFIG, session, params, 5, control="bfloat16")
        assert not ok and facts["logit_rel_err"] > facts["limits"]["median"]
    finally:
        session.close()


# ----------------------------------------------------------------------
# the selection
# ----------------------------------------------------------------------

def test_the_bisection_finds_each_rows_kth_largest_exactly():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    scores = rng.standard_normal((7, 50)).astype(np.float32)
    scores[0, :5] = -np.inf
    scores[1] = -scores[1] ** 2              # all negative
    scores[2, 10:20] = 0.0                   # ties, and both zeros
    scores[2, 12] = -0.0
    for k in (1, 8, 49, 50):
        keys, edge = sparse_latent._kth_largest(jnp.asarray(scores), k)
        kept = np.asarray(keys >= edge[:, None])
        want = np.sort(scores, axis=1)[:, ::-1][:, k - 1]
        for row in range(7):
            if row == 2:      # -0.0 sorts below 0.0: no choice hangs on it
                continue
            assert (kept[row] == (scores[row] >= want[row])).all(), (k, row)
            assert kept[row].sum() >= k


def test_the_selected_sets_are_the_references_away_from_near_ties():
    """The program's mask of a whole sequence and its decode step's
    choice, from the same indexer operands, against the reference's
    `selection`: the same rows wherever the reference's last kept and
    first left-out scores are a thousandth of the kept spread apart."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    t, heads, dim, rope, top = 128, 16, 16, 8, 8
    normed = rng.standard_normal((t, 64)).astype(np.float32)
    c_q = rng.standard_normal((t, 32)).astype(np.float32)
    iq, ik, iw = (rng.standard_normal(s).astype(np.float32) * 0.3
                  for s in ((heads * dim, 32), (dim, 64), (heads, 64)))
    gamma, beta = np.ones(dim, np.float32), np.zeros(dim, np.float32)
    keep, margin, keys = reference.selection(
        normed, c_q, iq, ik, gamma, beta, iw, heads=heads, dim=dim,
        rope=rope, theta=50000.0, top_k=top, eps=1e-5)
    keep, clear = np.asarray(keep), np.asarray(margin) > 1e-3
    assert clear.sum() > 100 and (keep.sum(1)[top:] == top).all()
    # the program's operands: the reference's rotated queries and keys
    with jax.default_matmul_precision("highest"):
        q = (c_q @ iq.T).reshape(t, heads, dim)
        q = np.concatenate([np.asarray(reference._rotary(
            jnp.asarray(q[..., :rope]).transpose(1, 0, 2),
            50000.0)).transpose(1, 0, 2), q[..., rope:]], axis=-1)
        w = (normed @ iw.T) * heads ** -0.5 * dim ** -0.5
    mine = np.asarray(sparse_latent._selection(
        jnp.asarray(q), keys, jnp.asarray(w), top))[:t]
    assert (mine[clear] == keep[clear]).all()
    # a decode step at position t - 1 chooses that row's set
    cache = jnp.zeros((2, 1, 24, 128))
    ring = jnp.zeros((2, 1, dim, 128)).at[1, 0, :, :t].set(
        jnp.asarray(keys).T)
    score_of = np.asarray(sparse_latent._weighted_relu(
        jnp.einsum("hd,dk->hk", jnp.asarray(q[-1]), ring[1, 0]),
        jnp.asarray(w[-1])))[:t]
    assert set(np.argsort(-score_of)[:top]) == set(np.nonzero(keep[-1])[0])


def test_the_gathered_step_equals_the_masked_form_on_the_same_cache():
    """The decode step's node (absorbed, over the rows `lax.top_k`
    gathers) against the whole-sequence node's last row (up-projected,
    under the mask) on the same rows: the same numbers, re-associated."""
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    t, h, nope, rope, value, rank, q_rank = 40, 4, 8, 8, 12, 24, 32
    heads_i, dim, top = 16, 16, 8

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    c_q, latent = draw(1, t, q_rank), draw(1, t, rank + rope)
    qb, kvb = draw(h * (nope + rope), q_rank) * 0.3, draw(
        h * (nope + value), rank) * 0.3
    index_q, index_k, index_w = (draw(1, t, heads_i * dim),
                                 draw(1, t, dim), draw(1, t, heads_i))
    sizes = dict(num_heads=h, rope_dim=rope, value_dim=value,
                 index_heads=heads_i, top_k=top)
    want = sparse_latent.sparse_latent_attention(
        c_q, qb, latent, kvb, index_q, index_k, index_w, nope_dim=nope,
        theta=1.0e4, **sizes)[0, -1]
    # the step's own operands: the last position's queries, turned
    q = c_q[0, -1] @ qb.T
    q_rope = sparse_latent._attn._rotate(
        q[None, None, h * nope:], jnp.full((1, 1), t - 1), h, 1.0e4)
    cache = jnp.zeros((2, 1, rank + rope, 64)).at[1, 0, :, :t - 1].set(
        latent[0, :-1].T)
    keys = jnp.zeros((2, 1, dim, 64)).at[1, 0, :, :t - 1].set(
        index_k[0, :-1].T)
    got, cache, keys = sparse_latent.sparse_latent_cached_attention(
        q[None, None, :h * nope], q_rope, latent[:, -1:], kvb,
        index_q[:, -1:], index_k[:, -1:], index_w[:, -1:], cache, keys,
        jnp.ones((1,)), jnp.full((1,), t - 1.0), **sizes)
    _close(got[0, 0], want, 1e-5)
    _close(cache[1, 0, :, t - 1], latent[0, -1], 1e-6)
    _close(keys[1, 0, :, t - 1], index_k[0, -1], 1e-6)


# ----------------------------------------------------------------------
# the per-kind spec, the cache's entries, the counters
# ----------------------------------------------------------------------

def test_cache_spec_declares_each_kinds_entries():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(5, 128)
    assert list(spec) == ["latent_cache_0", "index_cache_0",
                          "latent_cache_1", "index_cache_1",
                          "latent_cache_2", "latent_cache_3",
                          "latent_cache_4"]
    assert spec["latent_cache_0"] == ("latent", (5, 1, 24 + 8, 128))
    assert spec["index_cache_0"] == ("index", (5, 1, 16, 128))
    # a window layer's ring is its window's, whatever the session's length
    assert spec["latent_cache_2"] == ("latent", (5, 1, 32 + 8, 5))
    assert lm.cache_spec(5, 3)["latent_cache_2"].shape == (5, 1, 40, 3)
    assert lm.mixed_symbol(4) is None     # the kinds keep two programs


def test_the_published_configuration_declares_its_rings():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        config = json.load(f)
    spec = family.model(config).cache_spec(5, 16384)
    assert spec["latent_cache_1"].shape == (5, 1, 576, 16384)
    assert spec["index_cache_1"].shape == (5, 1, 128, 16384)
    assert spec["latent_cache_4"].shape == (5, 1, 1088, 513)
    page = sum(e.nbytes for e in spec.values()) // 5
    assert page == 4 * (2 * (576 + 128) * 16384 + 3 * 1088 * 513)


def test_the_layer_kinds_count_what_a_call_reads():
    lm = family.model(CONFIG)
    fill = lm.call_counters(positions=64, platform="cpu")
    assert fill["attn.prefill_positions"] == 5 * 64
    assert fill["attn.kernel_positions"] == 0
    assert fill["sparse.prefill_pairs"] == 2 * 64 * 65 // 2
    assert fill["sparse.prefill_kept"] == 2 * (8 * 9 // 2 + 56 * 8)
    assert fill["moe.routed_pairs"] == 4 * 64 * 2
    step = lm.call_counters(rows=3, lengths=[3, 40, 100], computed=4,
                            pages=10, max_len=128, platform="tpu")
    assert step["sparse.layer_steps"] == step["sparse.kernel_steps"] == 2
    assert step["sparse.context_positions"] == 2 * (4 + 41 + 101)
    assert step["sparse.selected_positions"] == 2 * (4 + 8 + 8)
    assert step["sparse.index_bytes"] == 2 * 3 * 4 * 16 * 128
    assert step["cache.index_bytes"] == 2 * 10 * 4 * 16 * 128
    assert step["mla.layer_steps"] == 5 and step["mla.kernel_steps"] == 0
    assert step["mla.ring_bytes"] == (2 * 4 * 32 * 20        # gathered rows
                                      + 3 * 4 * 40 * 3 * 5)  # whole rings
    assert step["kv.window_rows"] == 3 * 3
    assert step["kv.wrapped_rows"] == 3 * 2
    assert step["cache.window_bytes"] == 3 * 10 * 4 * 40 * 5
    assert step["cache.latent_bytes"] == (2 * 10 * 4 * 32 * 128
                                          + step["cache.window_bytes"])
    # a ring no longer than the selection is read whole
    short = lm.call_counters(rows=1, lengths=[3], computed=1, pages=2,
                             max_len=8, platform="cpu")
    assert short["sparse.layer_steps"] == 2
    assert short["sparse.kernel_steps"] == 0


@pytest.mark.parametrize("why,positions,platform,kernel_layers", [
    ("the cell's one bucket, lowered for the TPU", 15360, "tpu", 2),
    ("the same bucket off the TPU", 15360, "cpu", 0),
    ("a short bucket: its scores stay on the chip", 256, "tpu", 0),
    ("a bucket that is no multiple of 128", 15400, "tpu", 0)])
def test_a_prefill_books_the_masked_kernels_positions(why, positions,
                                                      platform,
                                                      kernel_layers):
    """PR 49: the two full layers' positions go through the TPU's masked
    kernel where `ops.sparse_latent.masked_block` says a program lowered
    for `platform` has it; the three window layers keep 0."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        lm = family.model(json.load(f))
    fill = lm.call_counters(positions=positions, platform=platform)
    assert fill["attn.prefill_positions"] == 5 * positions, why
    assert fill["attn.kernel_positions"] == kernel_layers * positions, why
    for i, kernel in enumerate([kernel_layers > 0] * 2 + [False] * 3):
        layer = lm._mixers[i].counters(i, positions=positions,
                                       platform=platform)
        assert layer["attn.kernel_positions"] == positions * kernel, (why, i)


def test_the_tenant_charges_and_counts_the_new_entries(held):
    """`add_generative_tenant` charges every entry; two requests through
    the batcher move `kv.*` (the index keys are no ring), `cache.*`,
    `sparse.*` and `mla.*`, and the session names no kind."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    names = ("kv.page_positions", "kv.window_rows", "kv.wrapped_rows",
             "cache.reserved_bytes", "cache.latent_bytes",
             "cache.index_bytes", "cache.window_bytes",
             "sparse.layer_steps", "sparse.context_positions",
             "sparse.selected_positions", "sparse.prefill_pairs",
             "mla.layer_steps", "serving.decode.dispatches")
    server = mx.serving.ModelServer({})
    try:
        session = server.add_generative_tenant(
            "lm", family.model(CONFIG), held, ctx=mx.cpu(), max_sessions=2,
            max_len=128, max_decode_tokens=8, seq_buckets=[64])
        page = 4 * (2 * (32 + 16) * 128 + 3 * 40 * 5)
        assert session._cache_bytes == 3 * page
        assert session._ring_lens.tolist() == [128, 128, 5, 5, 5]
        before = {n: telemetry.counter_value(n) for n in names}
        futs = [server.submit_generate("lm", TOKENS[:n], max_new_tokens=6)
                for n in (20, 40)]
        for f in futs:
            assert len(f.result(timeout=120).tokens) == 6
        moved = {n: telemetry.counter_value(n) - before[n] for n in names}
    finally:
        server.close()
        telemetry.set_enabled(was)
    steps = moved["serving.decode.dispatches"]
    rows = 2 * 5     # each session's five decode steps
    assert steps >= 5 and moved["sparse.layer_steps"] == 2 * steps
    assert moved["mla.layer_steps"] == 5 * steps
    assert moved["sparse.prefill_pairs"] == 2 * 2 * 64 * 65 // 2
    assert moved["sparse.selected_positions"] == 2 * rows * 8
    assert moved["sparse.context_positions"] == 2 * sum(
        n + k for n in (21, 41) for k in range(5))
    assert moved["kv.window_rows"] == moved["kv.wrapped_rows"] == 3 * rows
    assert moved["kv.page_positions"] == pytest.approx(
        rows * (2 * 128 + 3 * 5) / 5)
    reserved = moved["cache.reserved_bytes"]
    assert moved["cache.latent_bytes"] + moved["cache.index_bytes"] \
        == reserved > 0
    assert moved["cache.index_bytes"] * 3 == (
        moved["cache.latent_bytes"] - moved["cache.window_bytes"]) * 1.5


# ----------------------------------------------------------------------
# one chip's share of the expert layer
# ----------------------------------------------------------------------

def _expert_layer(p, i, first, count, shared, x):
    """Layer i's `mx.sym.MoE` node alone on `x (T, d)`, holding experts
    `first` .. `first + count`, with or without the shared expert."""
    names = ["router_weight", "router_bias", "gate_weight", "down_weight",
             "up_weight"]
    if shared:
        names += ["shared_gate_weight", "shared_down_weight",
                  "shared_up_weight"]
    v = [mx.sym.Variable(n) for n in ["data"] + names]
    node = mx.sym.MoE(*v, num_experts=16, hidden_size=32, k=2,
                      act_type="silu", gated=True, no_bias=True,
                      normalize=True, score_func="sigmoid", select_bias=True,
                      held_first=first, held_count=count,
                      shared_size=32 if shared else 0, return_load=True)
    values = {n: p["l%d_%s" % (i, n)] for n in names}
    for n in ("gate_weight", "down_weight", "up_weight"):
        values[n] = values[n][first:first + count]
    exe = node.bind(mx.cpu(), dict({"data": mx.nd.array(x)}, **{
        n: mx.nd.array(a) for n, a in values.items()}), grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


def test_the_shares_and_the_shared_expert_once_make_the_layer(uncut):
    """THE SHARE TEST: the outputs of one expert layer held as experts
    0-1, 2-3, ... 14-15 (eight chips a layer, the router 16 wide, 2 a
    token by score + bias, renormalised over the two, on all), the shared
    expert counted once, add up to what the uncut reference gives for the
    whole layer; each share's load counts its own experts' pairs, which
    together are every pair."""
    import jax

    x = np.random.default_rng(2).standard_normal((24, 64)).astype(np.float32)
    layer = lambda n: uncut["l1_%s" % n]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.expert_layer(
            x, layer("router_weight"), layer("router_bias"),
            layer("gate_weight"), layer("up_weight"), layer("down_weight"),
            (layer("shared_gate_weight"), layer("shared_up_weight"),
             layer("shared_down_weight")), 2, True, 1.0, 0)[0])
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


def test_many_pairs_go_through_the_experts_in_pieces(monkeypatch):
    """A call whose pairs' rows pass `_PAIR_BYTES` sorts a piece of its
    tokens at a time, each within it: the same numbers, the loads summed;
    a token whose own rows pass it is refused."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((48, 16)).astype(np.float32))
    logits = jnp.asarray(rng.standard_normal((48, 8)).astype(np.float32))
    weights = tuple(jnp.asarray(rng.standard_normal(s).astype(np.float32))
                    for s in ((4, 16, 8), (4, 8, 16), (4, 16, 8)))
    args = dict(act="silu", gated=True, score="sigmoid", held=(2, 4))
    calls = []
    whole = moe._dropless
    monkeypatch.setattr(moe, "_dropless", lambda x, *a: (
        calls.append(x.shape[0]), whole(x, *a))[1])
    with jax.default_matmul_precision("highest"):
        want, load = moe.dropless_experts(x, logits, 2, weights, **args)
        # 17 tokens' rows: pieces of 16, the most that divide 48
        monkeypatch.setattr(moe, "_PAIR_BYTES", 17 * 2 * 16 * 4)
        got, load_pieces = moe.dropless_experts(x, logits, 2, weights,
                                                **args)
        monkeypatch.setattr(moe, "_PAIR_BYTES", 2 * 16 * 4 - 1)
        with pytest.raises(ValueError, match="no piece"):
            moe.dropless_experts(x, logits, 2, weights, **args)
    assert calls == [48, 16]
    _close(got, want, 1e-6)
    assert (np.asarray(load) == np.asarray(load_pieces)).all()


def test_every_cell_sorts_its_pairs_at_once():
    """`_PAIR_BYTES` cuts no cell's bucket any more (PR 55): every pair's
    rows of this model's 15,360-position bucket pass it — until PR 55 it
    went through its experts in ten pieces of its tokens — but a held
    range gathers a PASS of its held pairs' rows at a time
    (`parallel.moe.pass_plan`), and that is within it; every other cell's
    largest call (a mixed step's: the longest bucket beside every slot)
    was within it as it was."""
    import importlib

    from benchmarks.harness import spec
    from mxnet_tpu.parallel import moe

    bench = spec.load_benchmark()
    cut, plans = [], {}
    for row in bench["workloads"]:
        cell = spec.Cell(bench, row["name"])
        if "tenant" not in cell.traffic:
            continue
        lm = importlib.import_module(
            "benchmarks.families." + cell.config["family"]).model(cell.config)
        if not lm._routed():
            continue
        tenant = cell.traffic["tenant"]
        rows = max(tenant["seq_buckets"]) + tenant["max_sessions"]
        if rows * lm.experts_per_token * lm.d_model * 4 > moe._PAIR_BYTES:
            cut.append(row["name"])
        plans[row["name"]] = lm.expert_plan(rows)[1:3]
    assert cut == ["dots3note_longdoc_c8", "smallthinker_longctx_c16",
                   "longcatflash_turns_c16", "nemotron3nano_agent_c16"]
    # six experts a token of 2,688 (PR 64): the 49,200 pairs of its 8,192
    # bucket beside eight rows would be 529 MB of rows, but 32 of 128
    # experts are held: ONE pass of thirty-seven 512-row tiles, 204 MB
    assert plans.pop("nemotron3nano_agent_c16") == (1, 18944)
    # twelve experts a token of 6,144: the 24,672 pairs of its 2,048 bucket
    # beside eight rows would be 606 MB of rows, but 8 of the router's 768
    # columns are held (PR 62; the zero-compute experts' pairs gather no
    # row): ONE pass of one 512-row tile
    assert plans.pop("longcatflash_turns_c16") == (1, 512)
    # no held range there (PR 59: 64 of 64 experts): the 61,488 pairs of
    # its 10,240 bucket beside eight rows go in three pieces of 3,416
    # tokens, 210 MB of rows each
    assert plans.pop("smallthinker_longctx_c16") == (3, 0)
    assert {pieces for pieces, _ in plans.values()} == {1}
    # one pass of twelve 512-row tiles: 126 MB of rows, where every
    # pair's were 2.5 GB
    assert plans["dots3note_longdoc_c8"] == (1, 6144)
    assert plans["olmoe_offline"] == (1, 0)


# ----------------------------------------------------------------------
# what the spec refuses, and the layouts
# ----------------------------------------------------------------------

def test_a_kind_needs_its_own_sizes():
    args = family.model_args(CONFIG)
    for kind, missing in ((SPARSE, "index_topk"), (WINDOW, "window"),
                          (SPARSE, "kv_rank")):
        specs = {k: dict(v) for k, v in args["kind_specs"].items()}
        del specs[kind][missing]
        with pytest.raises((ValueError, KeyError)):
            TransformerLM(**dict(args, kind_specs=specs))
    with pytest.raises(ValueError, match="kind_specs"):
        TransformerLM(**dict(args, kind_specs={"attention_": {}}))
    specs = {k: dict(v, rope_dim=7) for k, v in args["kind_specs"].items()}
    with pytest.raises(ValueError, match="rope_dim even"):
        TransformerLM(**dict(args, kind_specs=specs))


def test_unpermuted_rows_are_another_model(params, held):
    """`checkpoint_layout` is what ties the program's `W_qb` (by kind) to
    the reference's (head by head): without it the two disagree."""
    got = _score(family.model(CONFIG), held, TOKENS[:40])
    want = np.asarray(reference.logits(params, CONFIG, TOKENS[:40]))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()
    rows = family.layout_rows(CONFIG, "full_attention")
    assert sorted(rows) == list(range(4 * 16))
    assert rows[:9].tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 16]
    assert rows[32:41].tolist() == [8, 9, 10, 11, 12, 13, 14, 15, 24]
