"""The cell `nemotron3nano_agent_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `nemotron_h` configuration
— five layers of ONE sublayer each, four held of sixteen ungated experts
— as small as it can be: tier-1 runs this directory file after file on one
worker.  It pins this cell's own entries, traffic and configuration —
nothing about any other cell; the family's check is held to wrong models
at a tiny size in `tests/test_nemotron_h.py`."""
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
CELL = "nemotron3nano_agent_c16"
CONFIG = "nemotron-3-nano-30b-a3b"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"ssm.state_mb_step_n": "session state",
       "ssm.step_kernel_share_n": "session state",
       "moe.held_share_n": "expert layer",
       "moe.ungated_share_n": "expert layer"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/nemotron_h_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    # the traced run alone: `test_rehearsal.py` holds every cell's
    # untraced rehearsal, this one's among them, to its end-to-end names
    cell, clock = _cell(), device.CompileClock()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 64, seconds=2.0,
                              trace=1)
    return cell, json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], clock, time.perf_counter())))


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 64's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "agent_closed_c16" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/nemotron-3-nano-30b-a3b.json"
    assert conf["source"] == real.config["source"]
    assert conf["reduced"] == REDUCED
    assert real.config["family"] == "nemotron_h"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert set(NEW) | {
        "moe.experts_hit_share", "moe.pairs_per_hit_expert",
        "moe.rows_per_pair", "moe.kernel_share", "moe.placed_share",
        "cache.state_share", "prefill.pad_share", "batcher.fill_sat",
        "batcher.prefill_share", "batcher.prefill_ms_sat",
        "batcher.decode_step_ms_sat", "batcher.mixed_share_sat",
        "batcher.runahead_share_sat", "batcher.stall_share_sat",
        "kv.reserved_over_used", "attn.kernel_share_sat",
        "device.decode_ms_sat", "device.prefill_us_per_pos_sat",
        "device.seen_share_sat", "device.idle_share_sat",
        "device.peak_mem_gb", "startup.compile_s"} <= names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
            assert m["source"] == "program_counter"
            assert m["layer"] == NEW[m["name"]]
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    # two accepted readings under names of this cell's: one reading each
    assert (spec.metric_definition("moe.held_share_n")
            == spec.metric_definition("moe.held_share"))
    assert (spec.metric_definition("ssm.state_mb_step_n")
            == spec.metric_definition("ssm.state_mb_step"))
    share = spec.metric_definition("ssm.step_kernel_share_n")["args"]
    assert share == {"num": [{"counter": "ssm.step_kernel_bytes"}],
                     "den": [{"counter": "ssm.state_bytes"}], "scale": 100.0}
    share = spec.metric_definition("moe.ungated_share_n")["args"]
    assert share == {"num": [{"counter": "moe.ungated_pairs"}],
                     "den": [{"counter": "moe.pairs"}], "scale": 100.0}
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {
        "max_sessions": 8, "max_len": 8704, "max_decode_tokens": 256,
        "seq_buckets": [4096, 5120, 6144, 7168, 8192]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert traffic["requests"]["prompt_len"] == {
        "median": 6144, "sigma": 0.25, "min": 4096, "max": 8192}
    out = traffic["requests"]["output_len"]
    assert out["min"] == out["max"] == out["median"] == 256
    assert traffic["trace_seconds"] == 4.0
    # the longest prompt and the longest answer fit a ring
    assert 8192 + 256 <= 8704


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == REDUCED
    pattern = config["hybrid_override_pattern"]
    assert config["num_hidden_layers"] == len(pattern) == 13
    # the pattern's first two attention periods whole
    assert pattern == "MEMEM*EMEMEM*"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        6, 5, 2)
    assert config["published"]["hybrid_override_pattern"].startswith(pattern)
    assert config["n_routed_experts"] == 32
    assert config["held_experts"] == [0, 32]
    assert config["router_experts"] == 128 and config["vocab_size"] == 32768
    assert config["num_experts_per_tok"] == 6
    assert config["deployment"]["chips_per_layer"] == 4
    assert config["published"] == dict(
        config["published"], num_hidden_layers=52, n_routed_experts=128,
        vocab_size=131072)
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    assert {"inner_width", "no_rotary", "dtype", "stream", "mamba",
            "attention", "experts", "layouts", "weights", "not_run"} <= set(
        config["assumed"])
    assert "1,920" in config["assumed"]["layouts"]
    # the guide's floors: a whole period and four layers, eight experts,
    # an eighth of the vocabulary
    assert config["n_routed_experts"] >= 8
    assert 8 * config["vocab_size"] >= 131072
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert config["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(changed) == sorted(REDUCED)


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a page and a set (`reduced_why`, PERF.md
    section 4), a step's bytes and a prefill's operations (PERF.md sections
    5-6), and the kernels' operations and bytes, pinned."""
    from benchmarks.families import nemotron_h as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p, *tails: sum(  # noqa: E731
        math.prod(s) for n, s in shapes.items()
        if n.startswith(p) and n.endswith(tails or ("",)))
    d = 2688
    mamba = 10304 * d + d * 4096 + 4 * 6144 + 6144 + 3 * 64 + 4096 + d
    assert mamba == 38_744_896 == count("l0_")
    attention = 4608 * d + d * 4096 + d
    assert attention == 23_399_040 == count("l5_")
    assert family.stored_width(config) == 1920
    expert, stored = 2 * d * 1856, 2 * d * 1920
    shared, router = 2 * d * 3712, d * 128 + 128
    assert (expert, stored, shared) == (9_977_856, 10_321_920, 19_955_712)
    assert count("l1_") == 32 * expert + shared + router + d == 339_593_984
    # as published: 31.58 B, the card's 31.6B
    whole = (23 * mamba + 23 * (128 * expert + shared + router + d)
             + 6 * attention + 2 * 131072 * d + d)
    assert 31.57e9 < whole < 31.59e9
    assert count("embed_") == count("head_") == 32768 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert total == 2_153_400_832                 # 8.61 GB as published
    # as the program stores them (`TransformerLM.stored_params`: an
    # expert's 1,856 in whole lane tiles, 1,920): 8.83 GB on the device
    assert total + 5 * 32 * (stored - expert) == 2_208_451_072
    assert 8.82e9 < 4 * 2_208_451_072 < 8.85e9
    lm = family.model(config)
    spec_ = lm.cache_spec(1, tenant["max_len"])
    assert len(spec_) == 2 * (6 + 2)              # an E layer keeps nothing
    page = sum(e.nbytes for e in spec_.values())
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    assert state == 6 * 4 * (64 * 64 * 128 + 3 * 6144)        # 13.0 MB
    assert page - state == 2 * 2 * 4 * 2 * 128 * 8704         # 35.7 MB
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.437e9 < one_set < 0.439e9
    # what one 8-row step books: 2 x rows x the Mamba layers' pages, all of
    # them the step kernel's at four whole groups a grid step
    booked = lm.call_counters(rows=8, lengths=(6000,) * 8, computed=8,
                              pages=90, max_len=8704, platform="tpu")
    assert booked["ssm.state_bytes"] == 2 * 8 * state
    assert booked["ssm.step_kernel_bytes"] == booked["ssm.state_bytes"]
    assert booked["moe.routed_pairs"] == 5 * 8 * 6
    assert state == 6 * family.step_bytes(config, 1) // 2
    from mxnet_tpu.ops import ssm
    assert ssm.step_heads((9, 64, 64, 128), "tpu", 8) == 32
    # the 8,192 bucket: ONE pass of 18,432 sorted rows through the kernel,
    # returned by the row kernel; never the two fetching calls (a held range)
    assert lm.expert_plan(8192) == (49152, 1, 18432, True, False, True)
    assert lm.expert_plan(8)[1:] == (1, 0, False, False, False)
    hit = family.expected_experts_hit(config, 8)
    assert 10.0 < hit < 10.3                      # of 32 held, 12 pairs
    step = family.decode_bytes(config, rows=8, lengths=[6300] * 8,
                               experts_hit=hit)
    assert step["mamba"] == 4 * 6 * (10304 * d + d * 4096 + 4 * 6144)
    assert 0.92e9 < step["mamba"] < 0.94e9
    assert step["shared_and_router"] == 4 * 5 * (shared + d * 128)
    assert 2.0e9 < step["experts"] < 2.15e9
    assert step["state"] == 2 * 8 * state
    assert step["head"] == 4 * 32768 * d
    assert 4.2e9 < sum(step.values()) < 4.6e9     # ~5.4 ms at 819 GB/s
    flops = family.prefill_flops(config, 6144)
    assert 6.5e12 < sum(flops.values()) + 5 * flops["scan"] < 7.5e12
    # the kernels: the scan's block products, a step's state, a pass's
    # two segment matmuls
    assert family.scan_flops(config, 8192) == (
        2 * 64 * 128 * 128 * (8 * 128 + 4096) + 4 * 64 * 128 * 4096 * 128)
    assert family.step_bytes(config, 8) == 8 * 2 * 4 * (
        64 * 64 * 128 + 3 * 6144)
    assert family.ungated_matmul_flops(config, 18432) == (
        2 * 2 * 18432 * d * 1856)
    assert family.ungated_matmul_bytes(config, 18432, 32) == 4 * (
        32 * stored + 18432 * 2 * (d + 1920))


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    m = {k: v["value"] for k, v in metrics.items()}
    from benchmarks.families import nemotron_h as family

    tenant = cell.traffic["tenant"]
    spec_ = family.model(cell.config).cache_spec(1, tenant["max_len"])
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    total = sum(e.nbytes for e in spec_.values())
    # every bound set has the same split, so the share is the spec's
    assert m["cache.state_share"] == pytest.approx(100.0 * state / total)
    slots = tenant["max_sessions"]
    assert 2 * state * 1e-6 <= m["ssm.state_mb_step_n"] <= (
        2 * slots * state * 1e-6)
    # no kernel in a program lowered for the CPU
    assert m["ssm.step_kernel_share_n"] == 0
    assert m["moe.kernel_share"] == 0 and m["moe.placed_share"] == 0
    # four of sixteen experts held: a quarter of the pairs, by the draw;
    # every pair computed by two-matrix experts
    assert 10 < m["moe.held_share_n"] < 45
    assert m["moe.ungated_share_n"] == 100.0
    assert 0 < m["moe.experts_hit_share"] <= 100
    assert m["moe.pairs_per_hit_expert"] >= 1
    assert 0.0 <= m["prefill.pad_share"] < 75.0
    # the batcher runs a step ahead; a Mamba-2 model mixes nothing
    assert m["batcher.runahead_share_sat"] > 50
    assert m["batcher.mixed_share_sat"] == 0
    assert m["kv.reserved_over_used"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counter (the parent, under any cell's
    traced run): `ratio` finds `moe.ungated_pairs` nowhere and gives 0 over
    the pairs it does find, or — with neither — leaves the metric out; it
    does not raise."""
    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9, "moe.pairs": 40,
                            "moe.routed_pairs": 160,
                            "ssm.state_bytes": 4096},
               "histograms": {}}

    def read(name):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        return reader.read(w, **definition["args"])

    assert read("moe.ungated_share_n") in (None, 0.0)
    assert read("ssm.step_kernel_share_n") in (None, 0.0)
    assert read("moe.held_share_n") == 25.0
    w.after["counters"] = {}
    w.before["counters"].clear()
    for name in sorted(NEW):
        assert read(name) is None, name


def test_the_model_is_built_before_a_weight_is_drawn():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, so a program that
    cannot build a layer of one sublayer fails at once; the arguments are
    the configuration's, and name no model."""
    import inspect

    from benchmarks.families import nemotron_h as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert args["layer_types"] == [
        {"M": "mamba", "E": "none", "*": "attention"}[c]
        for c in "MEMEM*EMEMEM*"]
    assert args["ffn_types"] == [
        "routed" if c == "E" else "none" for c in "MEMEM*EMEMEM*"]
    assert (args["num_experts"], args["experts_per_token"],
            args["held_experts"]) == (128, 6, (0, 32))
    assert (args["expert_d_ff"], args["shared_d_ff"]) == (1856, 3712)
    assert (args["expert_act"], args["expert_gated"]) == ("relu2", False)
    assert (args["router_score"], args["router_bias"], args["route_norm"],
            args["route_scale"]) == ("sigmoid", True, True, 2.5)
    assert args["kind_specs"]["mamba"] == dict(
        heads=64, head_dim=64, state=128, groups=8, conv=4,
        chunk=128)
    assert (args["num_heads"], args["num_kv_heads"], args["head_dim"],
            args["positions"], args["tied_head"]) == (32, 2, 128, "none",
                                                      False)
    lm = family.model(config)
    assert lm.mixed_symbol(8) is None
    source = inspect.getsource(family.make_params)
    assert source.index("model(config)") < source.index("jax.random.key")
