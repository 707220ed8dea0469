"""The cell `mistralsmall4_reason_c32` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `mistral4` configuration.
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
CELL = "mistralsmall4_reason_c32"
CONFIG = "mistral-small-4-119b"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"mla.ring_mb_step", "mla.kernel_share", "cache.latent_share",
       "moe.held_share_m"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/mistral4_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 44, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 44's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "reason_closed_c32" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/mistral-small-4-119b.json"
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert real.config["family"] == "mistral4"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "kv.skipped_share_wide", "batcher.prefill_ms_sat",
                  "kv.reserved_over_used", "device.decode_ms_sat",
                  "device.seen_share_sat", "device.idle_share_sat",
                  "device.peak_mem_gb", "batcher.pack_ms_sat",
                  "batcher.emit_ms_sat", "batcher.prefill_share"} <= names
    assert not {"moe.held_share", "moe.held_share_q", "kv.wrapped_share",
                "cache.window_share", "cache.state_share",
                "kv.kernel_share", "kv.skipped_share_sat"} & names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "gen_tok_per_s"
            assert m["source"] == "program_counter"
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    # the held share under a third name: one reading, three cells
    assert (spec.metric_definition("moe.held_share_m")
            == spec.metric_definition("moe.held_share"))
    assert real.traffic["job"] == "generate"
    assert real.traffic["tenant"] == {
        "max_sessions": 16, "max_len": 6144, "max_decode_tokens": 4096,
        "seq_buckets": [768, 1024, 1536, 2048]}
    assert real.traffic["arrivals"] == {"process": "closed", "clients": 32}
    assert real.traffic["requests"] == {
        "prompt_len": {"median": 1024, "sigma": 0.4, "min": 512, "max": 2048},
        "output_len": {"median": 3072, "sigma": 0.0, "min": 3072,
                       "max": 3072}}
    assert real.traffic["trace_seconds"] == 4.0
    # the longest prompt with the largest budget fills a ring exactly
    assert 2048 + real.traffic["tenant"]["max_decode_tokens"] == (
        real.traffic["tenant"]["max_len"])


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # one chip's share of eight: an eighth of the experts and of the
    # vocabulary, the router as wide as published, four whole periods
    assert config["num_hidden_layers"] == 4
    assert config["n_routed_experts"] == 16
    assert config["held_experts"] == [0, 16]
    assert config["router_experts"] == 128 and config["vocab_size"] == 16384
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["published"] == dict(
        config["published"], num_hidden_layers=36, n_routed_experts=128,
        vocab_size=131072)
    assert {"softmax_scale", "router", "query_scale", "dtype", "gains",
            "block", "rotary", "layouts", "weights"} <= set(config["assumed"])
    assert all("why" in config["assumed"][k]
               for k in ("softmax_scale", "router", "query_scale"))
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    assert "vision tower" in config["not_run"]
    assert len(config["source"]) <= 200 and "config.json" in config["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Mistral-Small-4-119B-2603"]
    assert config["source"] == row["source_url"]
    assert config["router_experts"] == row["config"]["n_routed_experts"]
    assert 8 * config["vocab_size"] == row["config"]["vocab_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Bytes a layer, a page and a set (`reduced_why`, PERF.md section 4),
    and the hand roofline's inputs (PERF.md sections 5 and 6), pinned."""
    from benchmarks.families import mistral4 as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    mla = (4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144
           + 4096 * 4096)
    assert mla == 28_049_408 == family._mla_params(config)
    ffn = 4096 * 128 + 17 * 3 * 4096 * 2048
    norms = 2 * 4096 + 1024 + 256
    assert count("l0_") == count("l3_") == mla + ffn + norms
    assert count("embed_") == count("head_") == 16384 * 4096
    total = sum(math.prod(s) for s in shapes.values())
    assert 7.83e9 < 4 * total < 7.85e9            # 7.84 GB of weights
    lm = family.model(config)
    page = sum(e.nbytes for e in lm.cache_spec(1, tenant["max_len"]).values())
    assert page == 4 * 320 * 6144 * 4 == 31_457_280      # 31.5 MB
    # under 4% of what per-head K and V rings of 32 x 128 would hold
    assert page / (4 * 2 * 32 * 128 * 6144 * 4) == 320 / 8192 < 0.04
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.53e9 < one_set < 0.54e9
    assert 5.3e9 < 10 * one_set < 5.4e9            # ten bound sets
    # a 16-row step: 6.4 of 16 held experts a layer under uniform routing
    hit = family.expected_experts_hit(config, 16)
    assert 6.3 < hit < 6.5
    assert family.expert_bytes(config, 1) == 4 * 3 * 4096 * 2048
    # THE KERNEL'S BYTES: each row's ONE page of 320 float32 lines to the
    # block of 768 that holds its length, in each of the four layers
    assert family.ring_bytes(config, [767, 768, 2560]) == 4 * 1280 * (
        768 + 1536 + 3072)
    assert family.ring_bytes(config, [6143], ring_len=6144) == 4 * 1280 * 6144
    assert family.ring_bytes(config, [1000], block=512) == 4 * 1280 * 1024
    step = family.step_bytes(config, rows=16, lengths=[2560] * 16,
                             experts_hit=hit)
    assert step["experts"] == pytest.approx(4 * hit * 100_663_296)
    assert step["head"] == 4 * 16384 * 4096
    assert step["mla"] == 4 * 4 * mla
    assert step["ring"] == 16 * 4 * 1280 * 3072
    assert step["shared_and_router"] == 4 * 4 * (3 * 4096 * 2048 + 4096 * 128)
    assert 3.8e9 < sum(step.values()) < 4.1e9      # ~3.9 GB: 4.8 ms at 819 GB/s
    flops = family.step_flops(config, 16, [2560] * 16)
    assert flops["ring"] == 2 * 4 * 32 * 16 * 3072 * (2 * 256 + 64)
    assert flops["mla"] == 2 * 4 * 16 * mla


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
    # 4 of 16 experts are held and routing is near uniform
    assert 10.0 < metrics["moe.held_share_m"]["value"] < 45.0
    # every bound page is a latent page
    assert metrics["cache.latent_share"]["value"] == 100.0
    # off the TPU the kernel does not run, whole pages are read, and the
    # counters say so: two layers x 4 x 32 lines x 256 positions a row
    assert metrics["mla.kernel_share"]["value"] == 0.0
    assert metrics["kv.skipped_share_wide"]["value"] == 0.0
    assert 0 < metrics["mla.ring_mb_step"]["value"] <= 4 * 2 * 4 * 32 * 256e-6
    assert 0.0 < metrics["moe.experts_hit_share"]["value"] <= 100.0
    assert metrics["moe.pairs_per_hit_expert"]["value"] >= 1.0
    assert metrics["batcher.prefill_ms_sat"]["value"] > 0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds `mla.*` and `cache.latent_bytes` nowhere
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
    `TransformerLM` without the latent kind's arguments raises there."""
    from benchmarks.families import mistral4 as family
    from mxnet_tpu.models import transformer_lm

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert {"latent_q_rank", "latent_kv_rank", "rope_scaling",
            "query_scale"} <= set(args)
    assert "latent_attention" in transformer_lm._KINDS


@pytest.fixture(scope="module")
def check_inputs():
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import mistral4 as family

    cell = _cell()
    params = family.make_params(cell.config, 3, jax.devices()[0])
    # the init's 0.02 is small against the gains at these widths; x10
    # makes every part of the block matter
    params = {k: v if k.endswith("_gamma") else 10.0 * v
              for k, v in params.items()}
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    return cell, params, held


def _check(cell, params, held, control=None, **change):
    import mxnet_tpu as mx
    from benchmarks.families import mistral4 as family
    from mxnet_tpu.models import TransformerLM

    lm = TransformerLM(**dict(family.model_args(cell.config), **change))
    session = mx.serving.GenerativeSession("lm", lm, held,
                                           **cell.traffic["tenant"])
    try:
        return family.check_against_reference(
            cell.config, session, params, 7, 32, control=control, steps=48)
    finally:
        session.close()


def test_the_reference_check_steps_every_slot_at_once(check_inputs):
    """The rehearsal's four slots: one long row through the largest
    bucket, one short, two mid, 48 steps of the four-row program."""
    ok, facts = _check(*check_inputs)
    assert ok, facts
    assert facts["rows_a_step"] == 4 and facts["steps"] == 48
    assert facts["prompts"] == [56, 24, 25, 25]
    assert facts["buckets"] == [64, 32, 32, 64]
    assert facts["compared"] + facts["skipped"] == 4 * 49
    assert facts["logit_rel_err_worst"] < 1e-4


def test_the_reference_check_refuses_the_reference_in_bfloat16(check_inputs):
    ok, facts = _check(*check_inputs, control="bfloat16")
    assert not ok and facts["control"] == "bfloat16"
    assert facts["logit_rel_err"] > facts["limits"]["median"]
    assert facts["logit_rel_err_high"] > facts["limits"]["q90"]


@pytest.mark.parametrize("fault,change", [
    ("sigma_without_mscale", dict(attention_multiplier=16 ** -0.5)),
    ("plain_rope", dict(rope_scaling=None)),
    ("sigmoid_scores", dict(router_score="sigmoid")),
    ("weights_not_renormalised", dict(route_norm=False))])
def test_the_reference_check_refuses_a_seeded_fault(fault, change,
                                                    check_inputs):
    """Four faults a spec's argument makes (tests/test_mistral4.py seeds
    the nine ISSUE 44 names, those of the ops among them)."""
    ok, facts = _check(*check_inputs, **change)
    assert not ok, (fault, facts)
