"""The cell `trinitymini_reason_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `afmoe` configuration.  It
pins this cell's own entries, traffic and configuration — nothing about
any other cell."""
import argparse
import copy
import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "trinitymini_reason_c16"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
NEW = {"kv.wrapped_share", "cache.window_share", "moe.held_share"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == "trinity-mini"]
    conf["file"] = "configs/afmoe_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 38, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_and_its_traffic_are_the_issues_with_its_fallback_budgets():
    """ISSUE 38's traffic, letter for letter, but for the answers' budgets:
    with 2,048-4,096 (median 3,072, sigma 0.2) eight seeds spread 1.8% by
    quartiles, over the 1% the issue allows itself, and its fallback — min
    = max = median = 3,072, every seed the same budgets — is what the file
    holds (CHANGES.md, PR 38, has the tables; PERF.md section 6 their spreads)."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == "trinity-mini" and row["chips"] == 1
    assert row["traffic"] == "reason_closed_c16" and len(row["why"]) <= 200
    assert real.config["family"] == "afmoe"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "batcher.prefill_ms_sat", "kv.skipped_share_wide",
                  "kv.reserved_over_used", "device.decode_ms_sat"} <= names
    assert "kv.skipped_share_sat" not in names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            assert m["moves"] == "gen_tok_per_s"
            assert m["source"] == "program_counter"
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    assert real.traffic["job"] == "generate"
    assert real.traffic["tenant"] == {
        "max_sessions": 8, "max_len": 6144, "max_decode_tokens": 4096,
        "seq_buckets": [768, 1024, 1536, 2048]}
    assert real.traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert real.traffic["requests"] == {
        "prompt_len": {"median": 1024, "sigma": 0.4, "min": 512, "max": 2048},
        "output_len": {"median": 3072, "sigma": 0.2, "min": 3072,
                       "max": 3072}}
    assert real.traffic["trace_seconds"] == 4.0
    # the tenant is the issue's: the longest prompt with the longest
    # answer it allows fills a full ring exactly
    assert 2048 + real.traffic["tenant"]["max_decode_tokens"] == (
        real.traffic["tenant"]["max_len"])


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    assert config["num_dense_layers"] == 1
    assert config["layer_types"] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    # one chip's share of two: half the experts, half the vocabulary, the
    # router as wide as published
    assert config["num_experts"] == 64 and config["held_experts"] == [0, 64]
    assert config["router_experts"] == 128 and config["vocab_size"] == 100096
    assert config["deployment"]["chips_per_layer"] == 2
    assert config["published"] == dict(
        config["published"], num_hidden_layers=32, num_dense_layers=2,
        num_experts=128, vocab_size=200192)
    assert {"output_gate", "qk_norm", "rotary", "four_norms", "embedding",
            "selection_bias", "window", "weights", "layouts",
            "dtype"} <= set(config["assumed"])
    assert len(config["source"]) <= 200 and "config.json" in config["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"]
    assert config["source"] == row["source_url"]
    assert config["layer_types"] == row["config"]["layer_types"][1:6]
    assert config["router_experts"] == row["config"]["num_experts"]
    assert 2 * config["vocab_size"] == row["config"]["vocab_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


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
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    from benchmarks.families import afmoe as family

    tenant = cell.traffic["tenant"]
    lm = family.model(cell.config)
    spec_ = lm.cache_spec(tenant["max_sessions"] + 1, tenant["max_len"])
    window = cell.config["sliding_window"]
    rings = sum(e.nbytes for e in spec_.values() if e.shape[3] == window)
    total = sum(e.nbytes for e in spec_.values())
    assert 0 < rings < total
    assert metrics["cache.window_share"]["value"] == pytest.approx(
        100.0 * rings / total)
    # answers of 16-32 tokens after prompts of 6-32 pass a window of 16
    assert 30.0 < metrics["kv.wrapped_share"]["value"] <= 100.0
    # 4 of 8 experts are held and routing is near uniform
    assert 25.0 < metrics["moe.held_share"]["value"] < 75.0
    assert 0.0 < metrics["moe.experts_hit_share"]["value"] <= 100.0
    assert metrics["moe.pairs_per_hit_expert"]["value"] >= 1.0
    assert metrics["batcher.prefill_ms_sat"]["value"] > 0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0
    # the CPU's program reads whole pages
    assert metrics["kv.skipped_share_wide"]["value"] == 0.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds no denominator and leaves the metric out;
    it does not raise."""
    import importlib

    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9, "moe.pairs": 40,
                            "cache.reserved_bytes": 1 << 20},
               "histograms": {}}
    for name in sorted(NEW - {"cache.window_share"}):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        assert reader.read(w, **definition["args"]) is None, name


FAULTS = {
    "route_scale_dropped": dict(route_scale=1.0),
    "weights_not_renormalised": dict(route_norm=False),
    "selection_bias_dropped": dict(router_bias=False),
    "output_gate_dropped": dict(out_gate=False),
    "post_norms_dropped": dict(block_norm="input"),
    "qk_norm_dropped": dict(qk_norm=False),
    "rotary_on_the_full_layer": dict(positions={
        "window_attention": "rotary", "attention": "rotary"}),
    "window_one_too_long": dict(sliding_window=17),
    "window_one_too_short": dict(sliding_window=15),
    "router_scores_in_bfloat16": {},
}


@pytest.fixture(scope="module")
def check_inputs():
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import afmoe as family

    cell = _cell()
    params = family.make_params(cell.config, 3, jax.devices()[0])
    # the init's 0.02 is small against the gains at these widths; x10
    # makes every part of the block matter
    params = {k: v if k.endswith("_gamma") else 10.0 * v
              for k, v in params.items()}
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    return cell, params, held


def _check(cell, params, held, **change):
    import mxnet_tpu as mx
    from benchmarks.families import afmoe as family
    from mxnet_tpu.models import TransformerLM

    lm = TransformerLM(**dict(family.model_args(cell.config), **change))
    wanted = set(lm.prefill_symbol().list_arguments())
    mine = {k: v for k, v in held.items() if k in wanted}
    if change.get("out_gate") is False:   # [q | k | v | g] without its g
        width = (lm.num_heads + 2 * lm.num_kv_heads) * lm.d_head
        mine.update({k: mx.nd.array(v.asnumpy()[:width])
                     for k, v in mine.items() if k.endswith("_qkv_weight")})
    session = mx.serving.GenerativeSession("lm", lm, mine,
                                           **cell.traffic["tenant"])
    try:
        return family.check_against_reference(
            cell.config, session, params, 3,
            min(cell.traffic["tenant"]["seq_buckets"]))
    finally:
        session.close()


def test_the_reference_check_crosses_the_windows_edge(check_inputs):
    """On the CPU both sides multiply in float32, so every compared row
    agrees to rounding: the short prompts through the smallest bucket and
    the prompt 8 short of the largest, whose 320 decode steps are all
    produced from wrapped rings at this window of 16 (at the published
    2,048 the first eight are not)."""
    from benchmarks.families import afmoe as family

    ok, facts = _check(*check_inputs)
    assert ok and facts["logit_rel_err_worst"] < 1e-4
    assert facts["router_rel_err"] < 1e-5
    assert facts["prompts"] == [24] * family.CHECK_PROMPTS + [24]
    assert facts["steps"] == [8] * family.CHECK_PROMPTS + [320]
    assert facts["window"] == 16 and facts["wrapped_rows"] == 320
    assert facts["wrapped_compared"] >= family.MIN_WRAPPED_COMPARED == 56
    assert facts["compared"] + facts["skipped"] == 4 * 9 + 321
    assert facts["limits"] == {
        "median": family.LOGIT_RTOL, "short": family.LOGIT_RTOL_SHORT,
        "edge_q97": family.LOGIT_RTOL_EDGE, "router": family.ROUTER_RTOL,
        "near_tie": family.NEAR_TIE, "min_wrapped_compared": 56}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_check_refuses_one_seeded_fault_of_each_kind(
        fault, check_inputs, monkeypatch):
    """The same weights under a model with ONE part of the block wrong:
    the check says no, by the median or by the worst row."""
    from benchmarks.families import afmoe as family

    if fault == "router_scores_in_bfloat16":
        import jax.numpy as jnp

        from mxnet_tpu.parallel import moe

        monkeypatch.setattr(moe, "router_logits", lambda x, w: jnp.dot(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(
                jnp.float32))
    ok, facts = _check(*check_inputs, **FAULTS[fault])
    assert not ok, facts
    over = {"median": facts["logit_rel_err"] > family.LOGIT_RTOL,
            "short": facts["logit_rel_err_short"] > family.LOGIT_RTOL_SHORT,
            "edge": facts["logit_rel_err_edge"] > family.LOGIT_RTOL_EDGE,
            "router": facts["router_rel_err"] > family.ROUTER_RTOL}
    print("FAULT %s: median %.4f short %.4f edge %.4f router %.2g -> %s" % (
        fault, facts["logit_rel_err"], facts["logit_rel_err_short"],
        facts["logit_rel_err_edge"], facts["router_rel_err"],
        sorted(k for k, v in over.items() if v)))
    assert any(over.values()), facts


def test_the_byte_and_operation_counts_at_the_published_sizes():
    """The hand roofline's inputs (PERF.md section 5), pinned."""
    import math

    from benchmarks.families import afmoe as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    attention = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048
    assert attention == 27_262_976
    assert count("l0_") == attention + 3 * 2048 * 6144 + 4 * 2048 + 2 * 128
    expert_layer = (attention + 4 * 2048 + 2 * 128 + 2048 * 128 + 128
                    + 65 * 3 * 2048 * 1024)
    assert count("l1_") == count("l4_") == expert_layer == 436_478_336
    assert count("embed_") == count("head_") == 100096 * 2048
    total = sum(math.prod(s) for s in shapes.values())
    assert 8.85e9 < 4 * total < 8.90e9     # `reduced_why`'s 8.88 GB
    step = family.step_bytes(config, rows=8, lengths=[3000] * 8,
                             experts_hit=25)
    assert step["attention"] == 4 * 5 * attention
    assert step["experts"] == 4 * 4 * 25 * 3 * 2048 * 1024
    assert step["head"] == 4 * 100096 * 2048
    # a full layer's page to the block that holds 3,000 (3,072), a window
    # layer's whole ring
    assert step["kv"] == 8 * 2 * 4 * 4 * 128 * (3072 + 4 * 2048)
    assert 4.0e9 < sum(step.values()) < 5.0e9
    flops = family.prefill_flops(config, 2048)
    assert 1.38e12 < flops < 1.43e12   # 1.405 TFLOP: 7 ms at 197 TFLOP/s
