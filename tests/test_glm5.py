"""GLM-5's block and its multi-token-prediction module through
`TransformerLM` (`nextn` 1), `GenerativeSession` and the batcher: latent
attention with keys of 12 + 8 beside values of 16 under an indexer that
keeps the 8 best cached rows, a dense SwiGLU in layer 0 and then 2 of 16
sigmoid-routed experts times 2.5 beside a shared one, of which this model
holds a quarter; behind the trunk the MTP module, which the serving path
uses as the model's own DRAFT — a decode step verifies two positions a row
and emits one or two tokens — against the plain reference of the benchmark
(benchmarks/reference/glm5.py: float32 `jax.numpy` at "highest", no draft,
a token at a time) and against the SAME trunk served with no draft module.

Tiny widths (benchmarks/tests/data/rehearsal/configs/glm5_tiny.json: 2
layers + the module, hidden 64), both sides float32 on the CPU: errors are
float32 rounding; the bound 1e-4 is far above that and far below what one
bfloat16 pass leaves.  The file costs about 140 s.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops import registry
from mxnet_tpu.serving import GenerativeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import glm5 as family  # noqa: E402
from benchmarks.reference import glm5 as reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "rehearsal",
                       "configs", "glm5_tiny.json")) as f:
    CONFIG = json.load(f)
UNCUT = dict(CONFIG, n_routed_experts=16, held_experts=[0, 16])
RTOL = 1e-4
# three draws of the module's join: most drafts accepted, about half,
# almost none — by the WEIGHTS (the noise beside the embedding's half of
# `eh_proj`), never by a switch
DRAWS = {"most": 0.0, "half": 8.0, "hardly": 60.0}


def _params(config, join_noise=family.JOIN_NOISE, scale=10.0, seed=3):
    import jax

    drawn = family.make_params(config, seed, jax.devices("cpu")[0],
                               join_noise=join_noise)
    # the init's 0.02 is small against the gains at these widths: x10
    # makes every part of the block matter; what THE DRAW sets (the
    # embedding, the join) stays as drawn
    keep = ("_gamma", "_beta", "_bias", "_router_weight", "embed_weight",
            "mtp_eh_weight")
    return {k: np.asarray(v if k.endswith(keep) else scale * v)
            for k, v in drawn.items()}


@pytest.fixture(scope="module")
def uncut():
    """Every part of the block matters, the stream's half of the join
    too: what the reference and the seeded faults are held to."""
    return _params(UNCUT, DRAWS["half"])


@pytest.fixture(scope="module")
def served():
    """The weights as the family draws them (a stream that is large
    against the layers' updates): what the lossless tests serve."""
    return _params(CONFIG, DRAWS["most"], scale=1.0)


def _share(params, first, count):
    cut = ("_gate_weight", "_up_weight", "_down_weight")
    return {k: v[first:first + count]
            if k.endswith(cut) and "shared" not in k else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def params(uncut):
    return _share(uncut, 0, 4)


def _hold(params):
    return {k: mx.nd.array(v) for k, v in params.items()}


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max() / np.abs(want).max())


def _session(held, nextn=None, **kw):
    kw = dict(dict(max_sessions=4, max_len=128, max_decode_tokens=64,
                   seq_buckets=[64]), **kw)
    return GenerativeSession("lm", family.model(CONFIG, nextn=nextn), held,
                             **kw)


def _server(params, nextn=None, **kw):
    server = mx.serving.ModelServer({})
    kw = dict(dict(max_sessions=4, max_len=128, max_decode_tokens=64,
                   seq_buckets=[64]), **kw)
    session = server.add_generative_tenant(
        "lm", family.model(CONFIG, nextn=nextn), _hold(params), **kw)
    return server, session


def _generate(params, prompts, budgets, nextn=None, **kw):
    """Each prompt's reply through the batcher, and the counters' growth."""
    telemetry.set_enabled(True)
    before = telemetry.snapshot()["counters"]
    server, _ = _server(params, nextn, **kw)
    try:
        futures = [server.submit_generate("lm", p, max_new_tokens=b,
                                          timeout_ms=600e3)
                   for p, b in zip(prompts, budgets)]
        replies = [f.result(timeout=600) for f in futures]
    finally:
        server.close()
    after = telemetry.snapshot()["counters"]
    grown = {k: v - before.get(k, 0) for k, v in after.items()}
    return replies, grown


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "uncut"])
def test_score_symbol_matches_the_reference(which, uncut):
    """The trunk's whole-sequence logits (the module is no part of the
    scoring graph) against the reference, for one chip's share and for
    the uncut model."""
    config = CONFIG if which == "share" else UNCUT
    p = _share(uncut, 0, 4) if which == "share" else uncut
    tokens = [int(t) for t in np.random.default_rng(1).integers(0, 101, 64)]
    lm = family.model(config)
    pred = mx.Predictor(lm.score_symbol(), _hold(
        {k: v for k, v in p.items() if k in lm.score_symbol()
         .list_arguments()}), {"data": (1, 64)})
    pred.forward(data=np.asarray([tokens], np.float32))
    want = reference.logits(family.checkpoint_layout(p, config), config,
                            tokens)
    _close(pred.get_output(0).reshape(64, lm.vocab), want)


def test_the_check_passes_the_sound_program_and_refuses_the_faults(params):
    """The family's check at the tiny size, every slot live: the prefill's
    and 35 drafting steps' logits of BOTH positions and of the module
    against ONE forward of the reference over the sequence each row
    emitted, the emitted tokens the reference's greedy ones, every cache
    entry's rows — the module's own — the reference's; and the reference in
    bfloat16 and each seeded fault of the reference's is refused."""
    session = _session(_hold(params))
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5)
        assert ok, facts
        assert facts["prompts"] == [56, 16, 29, 42] and facts["steps"] == 35
        assert facts["logit_rel_err_worst"] < RTOL
        assert set(facts["cache_rel_errs"]) == set(session._spec)
        assert len(session._spec) == 6     # two layers' and the module's
        assert facts["cache_rel_err"] < RTOL
        assert facts["tokens_wrong"] == 0 and facts["tokens_judged"] > 30
        assert facts["drafted"] == 4 * 35
        assert 0 < facts["accepted"] < facts["drafted"]
        assert facts["compared"] > 100 and facts["draft_compared"] > 100
        for control, faults in [("bfloat16", ()), (None, ("normed_stream",)),
                                (None, ("crossed_halves",)),
                                (None, ("selection_dropped",)),
                                (None, ("short_topk",))]:
            ok, wrong = family.check_against_reference(
                CONFIG, session, params, 5, control=control, faults=faults)
            assert not ok, (control, faults, wrong)
    finally:
        session.close()


def test_the_long_row_runs_on_alone_past_the_selection(params):
    """Where the steps of all rows leave the long row short of
    `index_topk` + PAST_TOPK positions (the timed size: a bucket of 1,024
    and 128 steps under a selection of 2,048; here 4 steps after a prompt
    of 56 under 8 + 64), it runs on ALONE through the one-row program
    until it holds them, and its rows are compared like the others."""
    session = _session(_hold(params), max_len=256)
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5, steps=4)
    finally:
        session.close()
    assert ok, facts
    want = CONFIG["index_topk"] + family.PAST_TOPK
    assert facts["steps"] == 4 and facts["prompts"][0] == 56
    assert want <= facts["positions"][0] <= want + 1
    assert all(p <= n + 8 for p, n in zip(facts["positions"][1:],
                                          facts["prompts"][1:]))
    assert facts["drafted"] >= 4 * 4 + (want - 64) // 2
    assert facts["past_topk_rows"] > want - 56
    assert facts["logit_rel_err_worst"] < RTOL and facts["cache_rel_err"] < RTOL


# ----------------------------------------------------------------------
# lossless: the tokens are the trunk's own
# ----------------------------------------------------------------------

PROMPTS = [np.random.default_rng(7).integers(0, 101, n)
           for n in (20, 33, 47, 60, 25, 18)]


@pytest.fixture(scope="module")
def plain_replies(served):
    """The SAME trunk served with no draft module: the one-token step."""
    replies, grown = _generate(served, PROMPTS, [64 + i for i in range(6)],
                               nextn=0)
    assert "serving.mtp.drafts" not in grown
    return [r.tokens.tolist() for r in replies]


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_a_drafting_tenant_emits_the_plain_trunks_tokens(draw, served,
                                                         plain_replies):
    """Six requests on four slots (a retiring and an admitted session in
    the same window), 64-69 tokens each, under three draws of the join:
    the replies equal the no-draft tenant's token for token, hold exactly
    their budgets, and both branches of the verify rule were taken."""
    mine = dict(served, mtp_eh_weight=_params(
        CONFIG, DRAWS[draw])["mtp_eh_weight"])
    replies, grown = _generate(mine, PROMPTS, [64 + i for i in range(6)])
    for reply, plain in zip(replies, plain_replies):
        assert reply.finish_reason == "length"
        assert reply.tokens.tolist() == plain
    drafts, accepted = (grown["serving.mtp.drafts"],
                        grown["serving.mtp.accepted"])
    assert 0 < accepted < drafts                  # both branches
    share = accepted / drafts
    assert {"most": share > 0.8, "half": 0.4 < share < 0.8,
            "hardly": share < 0.3}[draw], share
    # rows, positions and tokens are counted apart
    tokens = sum(len(p) for p in plain_replies) - len(plain_replies)
    assert grown["serving.decode.tokens"] == tokens
    assert grown["serving.decode.row_steps"] <= drafts
    assert grown["serving.mtp.dropped_rows"] == drafts - accepted
    assert tokens <= grown["serving.decode.row_steps"] + accepted
    assert grown["mtp.step_bytes"] > grown["mtp.bytes"] > 0


def test_the_head_leans_on_one_cycle_through_the_vocabulary(served,
                                                            plain_replies):
    """THE DRAW's `HEAD_LEAN`: the token the head reads out of token t's
    embedding is t's successor on ONE cycle of all 101 tokens, so a greedy
    walk closes no loop inside a request — every reply's tokens differ."""
    after = (served["head_weight"] @ served["embed_weight"].T).argmax(0)
    walk, t = [], 0
    for _ in range(CONFIG["vocab_size"]):
        t = int(after[t])
        walk.append(t)
    assert t == 0 and len(set(walk)) == CONFIG["vocab_size"]
    for tokens in plain_replies:
        assert len(set(tokens)) == len(tokens)


def test_the_reference_decoder_agrees(served, plain_replies):
    """The reference's token-at-a-time greedy decoder on the first
    request: what both tenants emitted."""
    want = reference.greedy_decode(
        family.checkpoint_layout(served, CONFIG), CONFIG, PROMPTS[0], 24)
    assert plain_replies[0][:24] == want


@pytest.mark.parametrize("budgets", [(1, 2, 3, 4), (7, 8, 9, 10)])
def test_a_reply_holds_exactly_its_budget(budgets, params):
    """Budgets odd and even, of one token (the prefill's alone) and up: an
    accepted pair that would pass the budget is trimmed."""
    prompts = [PROMPTS[i][:12] for i in range(4)]
    replies, _ = _generate(params, prompts, budgets)
    plain, _ = _generate(params, prompts, budgets, nextn=0)
    for reply, want, budget in zip(replies, plain, budgets):
        assert len(reply.tokens) == budget
        assert reply.tokens.tolist() == want.tokens.tolist()
        assert reply.finish_reason == "length"


def test_a_session_runs_to_the_rings_end(params):
    """prompt + budget == max_len: the last step's second position lies
    ONE past the ring's end when the step before it accepted; the write is
    clamped, the row is dropped, the reply is exact."""
    prompts = [PROMPTS[i][:14 + i] for i in range(4)]
    budgets = [64 - len(p) for p in prompts]
    replies, _ = _generate(params, prompts, budgets, max_len=64,
                           seq_buckets=[32])
    plain, _ = _generate(params, prompts, budgets, nextn=0, max_len=64,
                         seq_buckets=[32])
    for reply, want in zip(replies, plain):
        assert reply.tokens.tolist() == want.tokens.tolist()


# ----------------------------------------------------------------------
# seeded faults of the PROGRAM: the check refuses each
# ----------------------------------------------------------------------

def _refused(params, monkeypatch, op, fn):
    real = registry.get_op(op).fn
    monkeypatch.setattr(registry.get_op(op), "fn",
                        lambda *a, **kw: fn(real, *a, **kw))
    session = _session(_hold(params))
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5)
    finally:
        session.close()
    assert not ok, facts
    return facts


def test_a_draft_accepted_without_comparing_is_refused(params, monkeypatch):
    def always(real, logits, fed, **kw):
        sampled, accept = real(logits, fed, **kw)
        return sampled, accept * 0 + 1

    _refused(params, monkeypatch, "_draft_verify", always)


def test_a_rejected_rows_stale_position_read_is_refused(params, monkeypatch):
    """The position advanced by 2 on a reject: the next step attends the
    stale latent row and index key the rejected draft left."""
    def by_two(real, sampled, accept, draft_logits, length, last_token,
               slot, **kw):
        token, state = real(sampled, accept, draft_logits, length,
                            last_token, slot, **kw)
        _, state_two = real(sampled, accept * 0 + 1, draft_logits, length,
                            last_token, slot, **kw)
        return token, state.at[2].set(state_two[2])

    _refused(params, monkeypatch, "_draft_commit", by_two)


def test_the_modules_rotary_position_off_by_one_is_refused(params,
                                                           monkeypatch):
    """The module's decode step turns its queries and keys as if at p + 1
    (its token is the NEXT one) where its prefill turned them at p."""
    from mxnet_tpu.models import transformer_lm

    real = transformer_lm._KindLatent._turn

    def late(self, t, name, heads, index, **rope):
        if index is not None and name.startswith(
                "l%d_" % self.lm.num_layers):
            index = index + 1
        return real(self, t, name, heads, index, **rope)

    monkeypatch.setattr(transformer_lm._KindLatent, "_turn", late)
    session = _session(_hold(params))
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5)
    finally:
        session.close()
    assert not ok and facts["draft_rel_err"] > 1e-3, facts


def test_the_modules_rings_left_unwritten_by_the_prefill_are_refused(
        params, monkeypatch):
    from mxnet_tpu.models import transformer_lm

    real = transformer_lm._SparseLatentAttention.prefill

    def unwritten(self, x, p, i, caches, slot, length):
        y, written = real(self, x, p, i, caches, slot, length)
        if i == self.lm.num_layers:
            written = [caches["latent_cache_%d" % i],
                       caches["index_cache_%d" % i]]
        return y, written

    monkeypatch.setattr(transformer_lm._SparseLatentAttention, "prefill",
                        unwritten)
    session = _session(_hold(params))
    try:
        ok, facts = family.check_against_reference(CONFIG, session, params,
                                                   5)
    finally:
        session.close()
    assert not ok and facts["cache_rel_err"] > 0.5, facts


# ----------------------------------------------------------------------
# one chip's share, the layouts, the bytes
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
                      route_scale=2.5, held_first=first, held_count=count,
                      shared_size=32 if shared else 0, return_load=True)
    values = {n: p["l%d_%s" % (i, n)] for n in names}
    for n in ("gate_weight", "down_weight", "up_weight"):
        values[n] = values[n][first:first + count]
    exe = node.bind(mx.cpu(), dict({"data": mx.nd.array(x)}, **{
        n: mx.nd.array(a) for n, a in values.items()}), grad_req="null")
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy(), exe.outputs[1].asnumpy()


@pytest.mark.parametrize("layer", [1, 2])
def test_the_shares_and_the_shared_expert_once_make_the_layer(layer, uncut):
    """THE SHARE TEST, for a trunk layer (1) and for the MTP block (2):
    the outputs of the expert layer held as experts 0-3, 4-7, 8-11, 12-15
    (four chips a layer, the router 16 wide, 2 a token by score + bias,
    renormalised over the two and times `routed_scaling_factor` 2.5, on
    all), the shared expert counted once, add up to what the uncut
    reference gives for the whole layer."""
    import jax

    x = np.random.default_rng(2).standard_normal((24, 64)).astype(np.float32)
    part = lambda n: uncut["l%d_%s" % (layer, n)]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.expert_layer(
            x, part("router_weight"), part("router_bias"),
            part("gate_weight"), part("up_weight"), part("down_weight"),
            (part("shared_gate_weight"), part("shared_up_weight"),
             part("shared_down_weight")), 2, True, 2.5, 0)[0])
    parts = [_expert_layer(uncut, layer, first, 4, first == 0, x)
             for first in range(0, 16, 4)]
    _close(sum(out for out, _ in parts), want, 1e-5)
    assert sum(load.sum() for _, load in parts) == 24 * 2
    # no share is the layer, and a scale of 1 is another model
    assert np.abs(parts[0][0] - want).max() > 1e-2 * np.abs(want).max()
    with jax.default_matmul_precision("highest"):
        unscaled = np.asarray(reference.expert_layer(
            x, part("router_weight"), part("router_bias"),
            part("gate_weight"), part("up_weight"), part("down_weight"),
            (part("shared_gate_weight"), part("shared_up_weight"),
             part("shared_down_weight")), 2, True, 1.0, 0)[0])
    assert np.abs(unscaled - want).max() > 1e-2 * np.abs(want).max()


def test_unpermuted_rows_are_another_model(params):
    """The layouts matter: the reference on the PROGRAM'S rows (rotary
    pairs rotate-half, `W_qb` by kind) is not the model."""
    tokens = [int(t) for t in np.random.default_rng(1).integers(0, 101, 40)]
    want = reference.logits(family.checkpoint_layout(params, CONFIG), CONFIG,
                            tokens)
    wrong = reference.logits(params, CONFIG, tokens)
    assert np.abs(np.asarray(wrong) - np.asarray(want)).max() > 1e-2 * np.abs(
        np.asarray(want)).max()


def test_the_programs_bytes_are_the_familys():
    """`mtp.bytes` / `mtp.step_bytes` as `TransformerLM.step_weight_bytes`
    counts them, against the family's byte functions, at the PUBLISHED
    sizes (shapes alone; nothing is allocated)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    load = np.zeros((5, 8), np.int64)
    load[:, :2] = 1        # two of the eight held experts hit a layer
    got = lm.step_weight_bytes(load)
    module, step = family.weight_bytes(config, 2)
    # (the program counts the norms' gains too: parts in a million)
    assert abs(got["mtp.bytes"] - module) < 1e-5 * module
    assert abs(got["mtp.step_bytes"] - step) < 1e-5 * step
    assert 0.20 < module / step < 0.25
    assert lm.token_state(5) == (3, 5)
    assert family.model(config, nextn=0).token_state(5) == (5,)


def test_cache_spec_declares_the_modules_own_entries():
    lm = family.model(CONFIG)
    spec = lm.cache_spec(5, 128)
    assert list(spec) == ["latent_cache_0", "index_cache_0",
                          "latent_cache_1", "index_cache_1",
                          "latent_cache_2", "index_cache_2"]
    assert spec["latent_cache_2"].shape == (5, 1, 24 + 8, 128)
    assert spec["index_cache_2"].shape == (5, 1, 16, 128)
    assert lm.mixed_symbol(4) is None      # no draft under a mixed step
    with pytest.raises(ValueError):
        family.model(CONFIG, nextn=2)


def test_every_other_cell_builds_the_plain_graphs():
    """No `nextn`, no new input, output or state: every other serving
    cell's model keeps the one-token contract — ``last_token (slots +
    1,)``, ``_token_feed`` in and ``_greedy_token`` out, no draft op, no
    node under a `__scope__` (but a carried routed branch's
    ``mx:moe.shortcut``, PR 62's cell alone, and two-matrix experts'
    ``mx:moe.ungated``, PR 64's), no `mtp_` parameter — so its
    programs stay what they were, to the compile cache's key (the graphs'
    JSON of all nine is the parent commit's, byte for byte: PERF.md
    section 6, PR 52)."""
    import importlib

    from benchmarks.harness import spec

    bench = spec.load_benchmark()
    drafting, scoped = [], set()
    for row in bench["workloads"]:
        cell = spec.Cell(bench, row["name"])
        if "tenant" not in cell.traffic:
            continue
        lm = importlib.import_module(
            "benchmarks.families." + cell.config["family"]).model(cell.config)
        slots = cell.traffic["tenant"]["max_sessions"]
        if lm.nextn:
            drafting.append(row["name"])
            continue
        assert lm.token_state(slots + 1) == (slots + 1,)
        spec_names = list(lm.cache_spec(slots + 1))
        graphs = [lm.prefill_symbol(), lm.decode_symbol()]
        mixed = lm.mixed_symbol(slots)
        graphs += [] if mixed is None else [mixed]
        for graph in graphs:
            text = graph.tojson()
            assert '"_draft' not in text and "mx:mtp" not in text
            if "__scope__" in text:
                assert ("mx:moe.shortcut" in text) != (
                    "mx:moe.ungated" in text)
                scoped.add(row["name"])
            assert '"_greedy_token"' in text
            args = graph.list_arguments()
            assert not [a for a in args if a.startswith("mtp_")]
            assert [a for a in args if a in spec_names] == sorted(
                spec_names, key=args.index)
            outs = graph.list_outputs()
            assert outs[1 + len(spec_names):3 + len(spec_names)] == [
                "token_output1", "token_output0"]
            assert len(outs) == 3 + len(spec_names) + len(
                lm.extra_outputs())
    assert drafting == ["glm5_mtp_reason_c8"]
    assert scoped == {"longcatflash_turns_c16", "nemotron3nano_agent_c16"}


def test_a_step_says_what_it_drafted_and_a_flight_what_it_emitted(
        served, tmp_path):
    """`serve.decode_step` of a drafting tenant carries `drafted` (the rows
    the step it dispatches carries) and `emitted` (the tokens the step it
    landed gave the clients); every landed decode flight leaves an
    `emitted` event in the flight recorder, whose counts add up to the
    counters'."""
    import re

    from mxnet_tpu import profiler
    from mxnet_tpu.obs import recorder

    fname = str(tmp_path / "profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    was = recorder.set_enabled(True)
    recorder.reset()
    profiler.profiler_set_state("run")
    try:
        replies, grown = _generate(served, PROMPTS[:4], [24] * 4)
    finally:
        profiler.profiler_set_state("stop")
        profiler.dump_profile()
        events = recorder.events()
        recorder.set_enabled(was)
    with open(fname) as f:
        steps = [e["args"] for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "serve.decode_step"]
    assert steps and all("drafted" in s and "emitted" in s for s in steps)
    assert sum(s["emitted"] for s in steps) == grown["serving.decode.tokens"]
    assert max(s["drafted"] for s in steps) == 4
    said = [dict((k, int(v)) for k, v in re.findall(r"(\w+)=(\d+)",
                                                    e["detail"]))
            for e in events if e["kind"] == "emitted"]
    assert sum(s["rows"] for s in said) == grown["serving.mtp.drafts"]
    assert sum(s["accepted"] for s in said) == grown["serving.mtp.accepted"]
    assert sum(s["tokens"] for s in said) == grown["serving.decode.tokens"]
    assert sum(len(r.tokens) for r in replies) == 4 * 24
