"""The cell `qwen3next_batch_c32` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `qwen3_next` configuration.
It pins this cell's own entries, traffic and configuration — nothing about
any other cell."""
import argparse
import copy
import json
import math
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "qwen3next_batch_c32"
CONFIG = "qwen3-next-80b-a3b"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json);
# the rehearsed window makes ~1,000 on an idle CPU and, shared with five
# other test workers, may make fewer
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"moe.held_share_q", "gdn.kernel_share", "kv.kernel_share"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/qwen3_next_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 40, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_and_its_traffic_are_the_issues_with_its_fallback_budgets():
    """ISSUE 40's cell, letter for letter, but for the answers' budgets:
    with 256-2,048 (median 768, sigma 0.5) the first eight seeds over two
    calls spread 0.95% by quartiles and a second eight 1.74%, over the 1%
    the issue allows itself, and its fallback — every budget 1,024, every
    seed the same budgets — is what the file holds (CHANGES.md, PR 40, has
    the tables; PERF.md section 6 their spreads)."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "batch_closed_c32" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/qwen3-next-80b-a3b.json"
    assert real.config["family"] == "qwen3_next"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "cache.state_share", "prefill.pad_share",
                  "gdn.state_mb_step", "kv.skipped_share_wide",
                  "batcher.prefill_ms_sat", "kv.reserved_over_used",
                  "device.decode_ms_sat", "device.seen_share_sat",
                  "device.idle_share_sat", "device.peak_mem_gb",
                  "batcher.pack_ms_sat", "batcher.emit_ms_sat",
                  "batcher.prefill_share"} <= names
    assert not {"moe.held_share", "kv.wrapped_share", "cache.window_share",
                "kv.skipped_share_sat"} & names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            assert m["moves"] == "gen_tok_per_s" and m["better"] == "higher"
            assert m["source"] == "program_counter"
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    assert (spec.metric_definition("moe.held_share_q")
            == spec.metric_definition("moe.held_share"))
    assert real.traffic["job"] == "generate"
    assert real.traffic["tenant"] == {
        "max_sessions": 16, "max_len": 4096, "max_decode_tokens": 2048,
        "seq_buckets": [768, 1024, 1536, 2048]}
    assert real.traffic["arrivals"] == {"process": "closed", "clients": 32}
    assert real.traffic["requests"] == {
        "prompt_len": {"median": 1024, "sigma": 0.4, "min": 512, "max": 2048},
        "output_len": {"median": 1024, "sigma": 0.0, "min": 1024,
                       "max": 1024}}
    assert real.traffic["trace_seconds"] == 4.0
    # the longest prompt with the longest answer fills a ring exactly
    assert 2048 + real.traffic["tenant"]["max_decode_tokens"] == (
        real.traffic["tenant"]["max_len"])


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # one chip's share of four: a quarter of the experts and of the
    # vocabulary, the router as wide as published, one whole period
    assert config["num_hidden_layers"] == config["full_attention_interval"] == 4
    assert config["num_experts"] == 128 and config["held_experts"] == [0, 128]
    assert config["router_experts"] == 512 and config["vocab_size"] == 37984
    assert config["deployment"]["chips_per_layer"] == 4
    assert config["published"] == dict(
        config["published"], num_hidden_layers=48, num_experts=512,
        vocab_size=151936)
    assert {"dtype", "gains", "block", "attention", "delta_rule", "experts",
            "layouts", "weights", "not_run"} <= set(config["assumed"])
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    assert len(config["source"]) <= 200 and "config.json" in config["source"]
    from benchmarks.families import qwen3_next as family

    assert family.layer_kinds(config) == ["linear_attention"] * 3 + [
        "attention"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    assert config["source"] == row["source_url"]
    assert config["router_experts"] == row["config"]["num_experts"]
    assert 4 * config["vocab_size"] == row["config"]["vocab_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Bytes a layer, a page and a set (`reduced_why`, PERF.md section 4),
    and the hand rooflines' inputs (PERF.md section 5), pinned."""
    from benchmarks.families import qwen3_next as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    linear = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048 + 4 * 8192 \
        + 2 * 32 + 128
    attention = 2048 * (16 * 512 + 2 * 2 * 256) + 4096 * 2048 + 2 * 256
    ffn = 2048 * 512 + 129 * 3 * 2048 * 512 + 2048 + 2 * 2048
    assert linear == 33_718_464 and attention == 27_263_488
    assert count("l0_") == count("l2_") == linear + ffn
    assert count("l3_") == attention + ffn
    assert count("embed_") == count("head_") == 37984 * 2048
    total = sum(math.prod(s) for s in shapes.values())
    assert 7.64e9 < 4 * total < 7.68e9            # 7.66 GB of weights
    lm = family.model(config)
    page = sum(e.nbytes for e in lm.cache_spec(1, tenant["max_len"]).values())
    ring = 2 * 2 * 256 * 4096 * 4
    state = 3 * 4 * (3 * 8192 + 128 * 32 * 128)
    assert page == ring + state == 23_363_584      # 16.8 + 6.6 MB
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.39e9 < one_set < 0.40e9
    assert 3.9e9 < 10 * one_set < 4.0e9            # ten bound sets
    # the delta rule's two programs, one layer
    assert family.scan_flops(config, 2048) == 32 * 32 * (
        4 * 64 * 64 * 128 + 64 * 64 * 256 + 6 * 64 * 128 * 128
        + 2 * 64 * 64 * 128)
    assert family.scan_bytes(config, 2048) == 4 * (
        2048 * (12352 + 4096) + 3 * 8192 + 32 * 128 * 128)
    assert family.step_bytes(config, 16) == 16 * 2 * 4 * (
        32 * 128 * 128 + 3 * 8192)
    assert family.step_flops(config, 16) == 16 * (
        7 * 32 * 128 * 128 + 2 * 4 * 8192)
    # a 16-row step: 34.6 of 128 held experts a layer under uniform routing
    hit = family.expected_experts_hit(config, 16)
    assert 34.5 < hit < 34.7
    assert family.expert_bytes(config, 1) == 4 * 3 * 2048 * 512
    step = family.decode_bytes(config, rows=16, lengths=[1500] * 16,
                               experts_hit=hit)
    assert step["experts"] == pytest.approx(4 * hit * 12_582_912)
    assert step["head"] == 4 * 37984 * 2048
    assert step["state"] == 3 * family.step_bytes(config, 16)
    # each row's page to the block of 512 that holds 1,500
    assert step["kv"] == 16 * 2 * 4 * 2 * 256 * 1536
    assert step["mixers"] == 4 * (3 * (linear - 2 * 32 - 128)
                                  + attention - 2 * 256)
    assert 2.9e9 < sum(step.values()) < 3.1e9      # ~3.0 GB: 3.7 ms at 819 GB/s


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
    assert 10.0 < metrics["moe.held_share_q"]["value"] < 45.0
    # off the TPU neither kernel runs, and the counters say so
    assert metrics["gdn.kernel_share"]["value"] == 0.0
    assert metrics["kv.kernel_share"]["value"] == 0.0
    assert metrics["kv.skipped_share_wide"]["value"] == 0.0
    assert 0.0 < metrics["moe.experts_hit_share"]["value"] <= 100.0
    assert metrics["moe.pairs_per_hit_expert"]["value"] >= 1.0
    assert 0.0 < metrics["cache.state_share"]["value"] < 100.0
    assert metrics["gdn.state_mb_step"]["value"] > 0
    assert metrics["batcher.prefill_ms_sat"]["value"] > 0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counter (the parent, under any cell's
    traced run): `ratio` finds `kv.kernel_positions` nowhere and gives
    0 over the page positions it does find, or — with neither — leaves
    the metric out; it does not raise."""
    import importlib

    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9, "moe.pairs": 40},
               "histograms": {}}
    for name in sorted(NEW):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        assert reader.read(w, **definition["args"]) is None, name
    w.after["counters"]["kv.page_positions"] = 4096
    definition = spec.metric_definition("kv.kernel_share")
    reader = importlib.import_module(
        "benchmarks.readers." + definition["reader"])
    assert reader.read(w, **definition["args"]) in (None, 0.0)


FAULTS = {
    "qk_repeated_as_n_mod_16": {},
    "beta_times_2": dict(linear_neg_eigval=True),
    "rotary_over_the_whole_head": dict(rotary_dim=None),
    "output_gate_dropped": dict(out_gate=False),
    "shared_gate_dropped": dict(shared_gate=False),
    "weights_not_renormalised": dict(route_norm=False),
    "gain_applied_as_w": {},
    "output_norm_gain_as_1_plus_gamma": {},
    "router_scores_in_bfloat16": {},
}


@pytest.fixture(scope="module")
def check_inputs():
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import qwen3_next as family

    cell = _cell()
    params = family.make_params(cell.config, 3, jax.devices()[0])
    # the init's 0.02 is small against the gains at these widths; x10
    # makes every part of the block matter
    small = ("_gamma", "_A_log", "_dt_bias", "_conv_weight")
    params = {k: v if k.endswith(small) else 10.0 * v
              for k, v in params.items()}
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    return cell, params, held


def _check(cell, params, held, gains=None, control=None, **change):
    import mxnet_tpu as mx
    from benchmarks.families import qwen3_next as family
    from mxnet_tpu.models import TransformerLM

    lm = TransformerLM(**dict(family.model_args(cell.config), **change))
    wanted = set(lm.prefill_symbol().list_arguments())
    mine = {k: v for k, v in held.items() if k in wanted}
    if change.get("out_gate") is False:   # [q | k | v | g] without its g
        width = (lm.num_heads + 2 * lm.num_kv_heads) * lm.d_head
        mine.update({k: mx.nd.array(v.asnumpy()[:width])
                     for k, v in mine.items() if k.endswith("_qkv_weight")})
    if gains is not None:                 # the program's stored gains, moved
        mine.update({k: mx.nd.array(gains(k, v.asnumpy()))
                     for k, v in mine.items() if k.endswith("_gamma")})
    session = mx.serving.GenerativeSession("lm", lm, mine,
                                           **cell.traffic["tenant"])
    try:
        return family.check_against_reference(
            cell.config, session, params, 3,
            min(cell.traffic["tenant"]["seq_buckets"]), control=control)
    finally:
        session.close()


def test_the_reference_check_steps_every_slot_at_once(check_inputs):
    """On the CPU both sides multiply in float32, so every compared row
    agrees to rounding: one prompt a slot — the one 8 short of the largest
    bucket, a short one, the others 7/12 of the smallest bucket through
    the buckets in turn — and their 128 decode steps, all rows a call
    through the decode program of as many rows as the tenant has slots."""
    from benchmarks.families import qwen3_next as family

    ok, facts = _check(*check_inputs)
    assert ok and facts["logit_rel_err_worst"] < 1e-4
    assert facts["router_rel_err"] < 1e-5
    slots = check_inputs[0].traffic["tenant"]["max_sessions"]
    assert facts["rows_a_step"] == slots == 4
    assert facts["prompts"] == [56, 24, 18, 18]
    assert facts["buckets"] == [64, 32, 32, 64]
    assert facts["steps"] == family.LONG_STEPS >= 64
    assert facts["compared"] + facts["skipped"] == slots * 129
    assert facts["long_compared"] >= family.MIN_LONG_COMPARED
    assert 0.0 < facts["remaining_share"] <= 1.0
    # off the TPU the attention reads whole pages: no row crosses a block
    assert facts["ring_block"] == 256 and facts["crossing_compared"] == 0
    assert facts["limits"] == {
        "median": family.LOGIT_RTOL, "q90": family.LOGIT_RTOL_HIGH,
        "worst": family.LOGIT_RTOL_WORST, "router": family.ROUTER_RTOL,
        "near_tie": family.NEAR_TIE,
        "min_long_compared": family.MIN_LONG_COMPARED,
        "min_crossing_compared": 0}


def test_the_check_at_the_cells_size_fills_sixteen_slots():
    """The rows of the check at the cell's own tenant: ISSUE 40's four
    short prompts and its one of 2,040, and eleven of 448 whose steps
    cross the first boundary of the 512 positions the ring kernel reads
    at a time, through every prefill bucket."""
    import types

    from benchmarks.families import qwen3_next as family
    from mxnet_tpu.ops.attention import decode_block

    tenant = spec.Cell(spec.load_benchmark(), CELL).traffic["tenant"]
    session = types.SimpleNamespace(_seq_ladder=tenant["seq_buckets"],
                                    _slots=tenant["max_sessions"])
    plans = family.check_plans(session, min(tenant["seq_buckets"]))
    assert len(plans) == 16
    assert plans[0] == (family.LONG, 2040, 2048)
    assert plans[1:5] == [(family.SHORT, 24, 768)] * 4
    assert [p[:2] for p in plans[5:]] == [(family.MID, 448)] * 11
    assert {p[2] for p in plans[5:]} == set(tenant["seq_buckets"])
    assert decode_block((17, 2, 256, 4096), "tpu") == 512
    assert 448 < 512 < 448 + family.LONG_STEPS
    assert 2040 < 2048 < 2040 + family.LONG_STEPS


def test_the_reference_check_refuses_the_reference_in_bfloat16(check_inputs):
    """THE CONTROL, through the check's own comparison: the reference
    with weights, activations and state in bfloat16, on the sequences the
    program generated, in the program's place."""
    from benchmarks.families import qwen3_next as family

    ok, facts = _check(*check_inputs, control="bfloat16")
    assert not ok and facts["control"] == "bfloat16"
    assert facts["logit_rel_err"] > family.LOGIT_RTOL


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_check_refuses_one_seeded_fault_of_each_kind(
        fault, check_inputs, monkeypatch):
    """The same weights under a model with ONE part of the block wrong:
    the check says no."""
    import jax.numpy as jnp

    from benchmarks.families import qwen3_next as family
    from mxnet_tpu.ops import gdn
    from mxnet_tpu.parallel import moe

    gains = None
    if fault == "router_scores_in_bfloat16":
        monkeypatch.setattr(moe, "router_logits", lambda x, w: jnp.dot(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(
                jnp.float32))
    elif fault == "qk_repeated_as_n_mod_16":
        # value head n takes q/k head n % H_k where it should take n // 2
        real = gdn.jnp.repeat
        monkeypatch.setattr(gdn.jnp, "repeat", lambda x, n, axis=None: (
            jnp.tile(x, (1,) * (x.ndim - 2) + (n, 1)) if axis == -2
            else real(x, n, axis=axis)))
        gdn._delta_rule.clear_cache()
    elif fault == "gain_applied_as_w":
        gains = lambda k, g: g if k.endswith("gnorm_gamma") else g - 1.0  # noqa: E731
    elif fault == "output_norm_gain_as_1_plus_gamma":
        gains = lambda k, g: g + 1.0 if k.endswith("gnorm_gamma") else g  # noqa: E731
    ok, facts = _check(*check_inputs, gains=gains, **FAULTS[fault])
    gdn._delta_rule.clear_cache()
    assert not ok, facts
    over = {"median": facts["logit_rel_err"] > family.LOGIT_RTOL,
            "short": facts["logit_rel_err_short"] > family.LOGIT_RTOL,
            "mid": facts["logit_rel_err_mid"] > family.LOGIT_RTOL,
            "long": facts["logit_rel_err_long"] > family.LOGIT_RTOL,
            "q90": facts["logit_rel_err_high"] > family.LOGIT_RTOL_HIGH,
            "worst": facts["logit_rel_err_worst"] > family.LOGIT_RTOL_WORST,
            "router": facts["router_rel_err"] > family.ROUTER_RTOL}
    print("FAULT %s: medians all %.4f short %.4f mid %.4f long %.4f q90 "
          "%.4f worst %.4f router %.2g -> %s" % (
              fault, facts["logit_rel_err"], facts["logit_rel_err_short"],
              facts["logit_rel_err_mid"], facts["logit_rel_err_long"],
              facts["logit_rel_err_high"], facts["logit_rel_err_worst"],
              facts["router_rel_err"],
              sorted(k for k, v in over.items() if v)))
    assert any(over.values()), facts
