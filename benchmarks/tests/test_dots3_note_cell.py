"""The cell `dots3note_longdoc_c8` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `dots3` configuration.
It pins this cell's own entries, traffic and configuration — nothing about
any other cell."""
import argparse
import copy
import importlib
import json
import math
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "dots3note_longdoc_c8"
CONFIG = "dots3-note-prev"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"sparse.read_share", "sparse.index_mb_step", "sparse.kernel_share",
       "sparse.prefill_keep_share", "cache.index_share", "moe.held_share_d",
       "cache.latent_share_d", "cache.window_share_d", "kv.wrapped_share_d",
       "mla.ring_mb_step_d", "mla.kernel_share_d", "prefill.pad_share_d"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SPARSE, WINDOW = "sparse_latent_attention", "window_latent_attention"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/dots3_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 48, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 48's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "longdoc_closed_c8" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/dots3-note-prev.json"
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "n_routed_experts", "vocab_size"]
    assert real.config["family"] == "dots3"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "kv.skipped_share_wide", "batcher.prefill_ms_sat",
                  "kv.reserved_over_used", "device.decode_ms_sat",
                  "device.seen_share_sat", "device.idle_share_sat",
                  "device.peak_mem_gb", "batcher.pack_ms_sat",
                  "batcher.emit_ms_sat", "batcher.prefill_share",
                  "batcher.mixed_share_sat", "attn.kernel_share_sat"} <= names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {"max_sessions": 4, "max_len": 16384,
                                 "max_decode_tokens": 256,
                                 "seq_buckets": [15360]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 8}
    assert traffic["requests"]["prompt_len"] == {
        "median": 14848, "sigma": 0.05, "min": 14336, "max": 15360}
    assert traffic["requests"]["output_len"] == {
        "median": 256, "sigma": 0.0, "min": 256, "max": 256}
    # one whole round (5.9 s on the chip) inside the traced part
    assert traffic["trace_seconds"] == 6.0


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "n_routed_experts", "vocab_size"]
    # one chip's share of 32: 8 of 256 experts, an eighth of the
    # vocabulary, the router as wide as published, the dense layer and
    # one whole period
    assert config["num_hidden_layers"] == 5
    assert config["layer_types"] == ["full_attention", "full_attention"] + [
        "sliding_attention"] * 3
    assert config["n_routed_experts"] == 8
    assert config["held_experts"] == [0, 8]
    assert config["router_experts"] == 256 and config["vocab_size"] == 19008
    assert config["deployment"]["chips_per_layer"] == 32
    assert config["published"] == dict(
        config["published"], num_hidden_layers=46, n_routed_experts=256,
        vocab_size=152064)
    assert {"lora_rescale", "head_gate", "indexer", "window",
            "softmax_scale", "rotary", "router", "dtype", "gains", "block",
            "layouts", "weights"} <= set(config["assumed"])
    assert all("why" in config["assumed"][k]
               for k in ("lora_rescale", "head_gate", "indexer", "window"))
    assert "bfloat16 pass" in config["assumed"]["indexer"]["precision"]
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    for part in ("vision tower", "audio encoder", "MTP"):
        assert part in config["not_run"]
    assert len(config["source"]) <= 200 and "config.json" in config["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == CONFIG]
    assert config["source"] == row["source_url"]
    assert config["router_experts"] == row["config"]["n_routed_experts"]
    assert 8 * config["vocab_size"] == row["config"]["vocab_size"]
    assert config["layer_types"] == row["config"]["layer_types"][:5]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
        elif key != "layer_types":
            assert config["published"][key] == value, key


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a page and a set (`reduced_why`, PERF.md
    section 4), and the hand rooflines' inputs (PERF.md section 5),
    pinned."""
    from benchmarks.families import dots3 as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    d = 5120
    full = (d * 1024 + 1024 * 128 * 192 + d * 576 + 512 * 128 * 256
            + 128 * 128 * d + 128 * d
            + 1024 * 64 * 128 + d * 128 + d * 64)
    slide = (d * 1024 + 1024 * 64 * 256 + d * 1088 + 1024 * 64 * 320
             + 64 * 128 * d + 64 * d)
    assert full == 144_048_128 == family.mixer_params(config,
                                                      "full_attention")
    assert slide == 90_832_896 == family.mixer_params(config,
                                                      "sliding_attention")
    dense, expert = 3 * d * 13824, 3 * d * 1536
    assert (dense, expert) == (212_336_640, 23_592_960)
    routed = 9 * expert + d * 256 + 256
    assert count("l0_") == full + dense + 2 * d + 1024 + 512 + 2 * 128
    assert count("l1_") == full + routed + 2 * d + 1024 + 512 + 2 * 128
    assert count("l2_") == count("l4_") == slide + routed + 2 * d + 2 * 1024
    assert count("embed_") == count("head_") == 19008 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert 7.28e9 < 4 * total < 7.30e9             # 7.29 GB of weights
    lm = family.model(config)
    page = sum(e.nbytes for e in lm.cache_spec(1, tenant["max_len"]).values())
    assert page == 4 * (2 * (576 + 128) * 16384 + 3 * 1088 * 513)
    assert 98e6 < page < 100e6                     # 99 MB
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.49e9 < one_set < 0.50e9
    assert 2.4e9 < 5 * one_set < 2.5e9             # five bound sets
    # a four-row step: 1 of 32 pairs lands on a held expert
    assert family.index_bytes(config, 4, 16384) == 2 * 4 * 4 * 128 * 16384
    lengths = [14900, 15000, 15100, 15200]
    rows = family.sparse_read_bytes(config, lengths, 16384)
    assert rows == (2 * 4 * 576 * 4 * 2048 + 3 * 4 * 1088 * 513 * 4)
    assert family.sparse_read_bytes(config, [100], 16384) == (
        2 * 4 * 576 * 101 + 3 * 4 * 1088 * 513)
    step = family.step_bytes(config, rows=4, lengths=lengths, experts_hit=1.0)
    assert step["mixers"] == 4 * (2 * full + 3 * slide)
    assert step["dense_mlp"] == 4 * dense
    assert step["experts"] == 4 * 4 * expert
    assert step["head"] == 4 * 19008 * d
    assert step["index"] == 67_108_864 and step["rows"] == rows
    # ~4.3 GB: 5.2 ms at 819 GB/s
    assert 4.1e9 < sum(step.values()) < 4.4e9
    flops = family.prefill_flops(config, 15360)
    assert 27e12 < flops["matmuls"] < 28.5e12
    assert flops["indexer"] == 2 * 2 * 64 * 128 * (15360 * 15361 // 2)
    assert 3.8e12 < flops["indexer"] < 3.9e12
    window_pairs = 513 * 514 // 2 + (15360 - 513) * 513
    assert flops["attention"] == (
        2 * 2 * 128 * (15360 * 15361 // 2) * 320
        + 3 * 2 * 64 * window_pairs * 384)
    assert 19e12 < flops["attention"] < 21e12


def test_untraced_rehearsal_is_correct_and_reports_tokens_per_second(results):
    cell, out = results
    result = out[0]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["metrics"]["gen_tok_per_s"]["value"] > 0


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out[1]["correct"] is True
    metrics = out[1]["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    # rows at 44-88 cached positions attend to the indexer's 8
    assert 8.0 < metrics["sparse.read_share"]["value"] < 20.0
    # a ring of 128 is longer than the selection: every step gathers
    assert metrics["sparse.kernel_share"]["value"] == 100.0
    # a bucket of 64: (36 + 56 x 8) of 2,080 causal pairs
    assert metrics["sparse.prefill_keep_share"]["value"] == pytest.approx(
        100.0 * (36 + 56 * 8) / 2080)
    # two layers' whole pages of 16 float32 lines x 128 a real row
    assert 0 < metrics["sparse.index_mb_step"]["value"] <= (
        4 * 2 * 4 * 16 * 128e-6)
    page = 2 * (32 + 16) * 128 + 3 * 40 * 5
    assert metrics["cache.index_share"]["value"] == pytest.approx(
        100.0 * 2 * 16 * 128 / page)
    assert metrics["cache.window_share_d"]["value"] == pytest.approx(
        100.0 * 3 * 40 * 5 / page)
    assert metrics["cache.latent_share_d"]["value"] == pytest.approx(
        100.0 * (1 - 2 * 16 * 128 / page))
    # every prompt is longer than the window of 5
    assert metrics["kv.wrapped_share_d"]["value"] == 100.0
    assert 0 < metrics["mla.ring_mb_step_d"]["value"]
    # no latent layer-step of either kind goes through the ring's kernel
    assert metrics["mla.kernel_share_d"]["value"] == 0.0
    # prompts of 44-64 in the ONE bucket of 64
    assert 0.0 <= metrics["prefill.pad_share_d"]["value"] <= 100.0 * 20 / 64
    # 4 of 16 experts are held and routing is near uniform
    assert 10.0 < metrics["moe.held_share_d"]["value"] < 45.0
    assert metrics["attn.kernel_share_sat"]["value"] == 0.0
    assert metrics["batcher.mixed_share_sat"]["value"] == 0.0
    assert metrics["batcher.prefill_ms_sat"]["value"] > 0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds `sparse.*` and `cache.index_bytes` nowhere
    and gives 0 over what it does find, or — with neither — leaves the
    metric out; it does not raise."""
    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9,
                            "cache.reserved_bytes": 4096},
               "histograms": {}}
    for name in sorted(NEW):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        assert reader.read(w, **definition["args"]) in (None, 0.0), name


def test_the_parent_fails_at_once_on_the_new_configuration():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, and a
    `TransformerLM` without `kind_specs` and the two kinds raises there."""
    from benchmarks.families import dots3 as family
    from mxnet_tpu.models import transformer_lm

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert set(args["kind_specs"]) == {SPARSE, WINDOW}
    assert args["kind_specs"][SPARSE]["index_topk"] == 2048
    assert args["kind_specs"][WINDOW]["window"] == 513
    assert {SPARSE, WINDOW} <= set(transformer_lm._KINDS)
    assert args["layer_types"] == [SPARSE, SPARSE, WINDOW, WINDOW, WINDOW]
    assert args["ffn_types"] == ["dense"] + ["routed"] * 4


# ----------------------------------------------------------------------
# the check, and the faults it has to refuse
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def check_inputs():
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import dots3 as family

    cell = _cell()
    params = family.make_params(cell.config, 3, jax.devices()[0])
    # the init's 0.02 is small against the gains at these widths; x10
    # makes every part of the block matter (the router's logits are of
    # their published size already, and the embedding's rows, which the
    # family draws large so that the chip's rounding at the edge of the
    # selection stays small, are of the matrices' size here: on the CPU
    # both sides choose the same rows)
    params = {k: v if k.endswith(("_gamma", "_beta", "_bias",
                                  "_router_weight"))
              else 0.2 / family.EMBED_STD * v if k == "embed_weight"
              else 10.0 * v for k, v in params.items()}
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    return cell, params, held


def _specs(cell, **changes):
    """The family's `kind_specs` with ``{kind: {key: value}}`` changed."""
    from benchmarks.families import dots3 as family

    specs = family.kind_specs(cell.config)
    for kind, change in changes.items():
        specs[kind].update(change)
    return dict(kind_specs=specs)


def _check(cell, params, held, control=None, **change):
    import mxnet_tpu as mx
    from benchmarks.families import dots3 as family
    from mxnet_tpu.models import TransformerLM

    lm = TransformerLM(**dict(family.model_args(cell.config), **change))
    wanted = set(lm.prefill_symbol().list_arguments())
    session = mx.serving.GenerativeSession(
        "lm", lm, {k: v for k, v in held.items() if k in wanted},
        **cell.traffic["tenant"])
    try:
        return family.check_against_reference(
            cell.config, session, params, 7, control=control)
    finally:
        session.close()


def test_the_reference_check_steps_every_slot_at_once(check_inputs):
    """The rehearsal's four slots through the one bucket of 64, then the
    72 steps of the four-row program that the rings of 128 leave the
    longest prompt (128 at the timed size): every compared row has more
    than the selection's 8 positions behind it, and every window ring has
    wrapped."""
    ok, facts = _check(*check_inputs)
    assert ok, facts
    assert facts["rows_a_step"] == 4 and facts["steps"] == 72
    assert facts["prompts"] == [56, 9, 26, 45]
    assert facts["compared"] + facts["skipped"] == 4 * 73
    assert facts["logit_rel_err_worst"] < 1e-4
    assert facts["cache_rel_err"] < 1e-4
    assert facts["selection_overlap"] == 1.0
    # the indexers' own margins are read and reported, and skip no row
    assert facts["index_margin_median"] > 0
    assert 0.0 <= facts["index_tie_share"] <= 1.0
    assert facts["limits"]["index_tie"] == 0.005


def test_the_reference_check_refuses_the_reference_in_bfloat16(check_inputs):
    ok, facts = _check(*check_inputs, control="bfloat16")
    assert not ok and facts["control"] == "bfloat16"
    assert facts["logit_rel_err"] > facts["limits"]["median"]
    assert facts["logit_rel_err_high"] > facts["limits"]["q90"]


FAULTS = ["selection_dropped", "topk_off_by_one", "relu_dropped",
          "index_rope_dropped", "head_gate_dropped", "lora_rescale_dropped",
          "window_one_too_long", "window_one_too_short",
          "rope_bases_swapped", "value_width_crossed",
          "router_scores_in_bfloat16"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_reference_check_refuses_a_seeded_fault(fault, check_inputs,
                                                    monkeypatch):
    """The same weights under a program with ONE part of the block wrong:
    the check says no."""
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.ops import sparse_latent
    from mxnet_tpu.parallel import moe

    cell = check_inputs[0]
    both = lambda **kw: {SPARSE: kw, WINDOW: kw}  # noqa: E731
    change = {
        "selection_dropped": {SPARSE: dict(index_topk=10 ** 6)},
        "topk_off_by_one": {SPARSE: dict(index_topk=7)},
        "head_gate_dropped": both(head_gate=False),
        "lora_rescale_dropped": both(lora_rescale=False),
        "window_one_too_long": {WINDOW: dict(window=6)},
        "window_one_too_short": {WINDOW: dict(window=4)},
        "rope_bases_swapped": {SPARSE: dict(rope_theta=50000.0),
                               WINDOW: dict(rope_theta=8.0e7)},
    }.get(fault, {})
    if fault == "relu_dropped":
        monkeypatch.setattr(
            sparse_latent, "_weighted_relu",
            lambda s, w: jnp.einsum("...h,...hk->...k", w, s))
    elif fault == "index_rope_dropped":
        turn = transformer_lm._KindLatent._turn
        monkeypatch.setattr(
            transformer_lm._KindLatent, "_turn",
            lambda self, t, *a, **rope: t if "rotary_dim" in rope
            else turn(self, t, *a, **rope))
    elif fault == "value_width_crossed":
        # a head's up-projected channels read as [v | k_nope]
        monkeypatch.setattr(
            sparse_latent, "_split_kv", lambda rows, nope, axis=-1: (
                jnp.take(rows, jnp.arange(rows.shape[axis] - nope,
                                          rows.shape[axis]), axis=axis),
                jnp.take(rows, jnp.arange(rows.shape[axis] - nope),
                         axis=axis)))
    elif fault == "router_scores_in_bfloat16":
        monkeypatch.setattr(moe, "router_logits", lambda x, w: jnp.dot(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(
                jnp.float32))
    ok, facts = _check(*check_inputs, **_specs(cell, **change))
    assert not ok, (fault, facts)
